"""Readers for ``smallthinker``: the decode step against its byte account,
the paged kernel's decode calls in BOTH cache groups against the larger of
its two rooflines at 7 queries a KV head, and the two counters of the
windowed pool (what of the never-wait reservation the traffic holds; how
many rows are still under the window). The kernel's calls are found by the
PROGRAM they run in and the SCOPE they run under (``readers/lfm2.
kernel_seconds``, in ``obs['kernel_call_s']``), not by a result type. Each
returns None where the run has nothing to read: no traced slice, no program
of that name, or records that lack the counter (the parent of the PR that
added it)."""

from __future__ import annotations

from benchmarks import peaks, smallthinker_bytes
from benchmarks.readers.laguna import _windows
from benchmarks.readers.tracing import module_step_ms
from benchmarks.trace import seconds_matching


def _tokens(ctx, windows) -> tuple[float, float]:
    """Cached tokens the records' tables name, summed: ``(full, window)``."""
    block = ctx.config['engine']['block_size']
    return (
        block * sum(r['kv_blocks_full'] for r in windows),
        block * sum(r['kv_blocks_window'] for r in windows),
    )


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (``smallthinker_bytes.
    decode_step_bytes``: held weights once, full-group KV over the rows'
    contexts, window-group KV over what the rows' windows hold, the rows
    written; from the decode records' block counts, so rounded up to whole
    blocks) over the HBM peak, over the device time of one step of the
    decode program: the share of the whole step."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    windows = [r for r in _windows(obs) if 'batch' in r]
    if step_ms is None or not windows:
        return None
    full, window = (t / len(windows) for t in _tokens(ctx, windows))
    rows = sum(r['batch'] for r in windows) / len(windows)
    bytes_moved = smallthinker_bytes.decode_step_bytes(
        ctx.config, full, window, rows
    )
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def paged_attn_roofline_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the paged
    kernel cannot do without, ``max(bytes / HBM peak, operations / bf16
    peak)`` of what the records' ``kv_blocks_full`` and ``kv_blocks_window``
    ask of it in the two groups' layers (times ``decode_steps``), over the
    device seconds of the kernel's calls in the programs and under the
    scopes that ``pattern`` finds among ``obs['kernel_call_s']``'s
    ``'<program> <scope>'`` keys."""
    calls = obs.get('kernel_call_s')
    if not calls:
        return None
    kernel_s = seconds_matching(calls, pattern)
    windows = _windows(obs, ctx.capture)
    if not kernel_s or not windows:
        return None
    steps = ctx.config['engine']['decode_steps']
    full, window = (steps * t for t in _tokens(ctx, windows))
    peak_flops, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    least_s = max(
        smallthinker_bytes.kv_bytes(ctx.config, full, window) / peak_bw,
        smallthinker_bytes.attn_flops(ctx.config, full, window) / peak_flops,
    )
    return 100.0 * least_s / kernel_s


def window_pool_held_share(ctx, obs):
    """``kv_blocks_window`` over ``kv_window_pool_blocks``, summed over the
    window's decode records: what of the windowed pool, sized so that it
    never makes a request wait, the decode rows really hold."""
    windows = [r for r in _windows(obs) if 'kv_window_pool_blocks' in r]
    pool = sum(r['kv_window_pool_blocks'] for r in windows)
    if not pool:
        return None
    return 100.0 * sum(r['kv_blocks_window'] for r in windows) / pool


def rows_under_window_share(ctx, obs):
    """``rows_under_window`` over the rows that ran, summed over the
    window's decode records: rows whose context is no longer than the
    window, whose window layers read all of it."""
    windows = [
        r for r in _windows(obs) if 'rows_under_window' in r and 'batch' in r
    ]
    rows = sum(r['batch'] for r in windows)
    if not rows:
        return None
    return 100.0 * sum(r['rows_under_window'] for r in windows) / rows
