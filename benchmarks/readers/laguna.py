"""Readers for a decoder with a full and a windowed cache group (``laguna``):
the decode step against its byte account, the paged-attention kernel against
the bytes its two groups' tables ask of it, and the windowed group's blocks
held over the blocks the same contexts fill. Each returns None where the run
has nothing to read: no traced slice, or a program whose records lack
``kv_blocks_full`` and ``kv_blocks_window`` (the parent of the PR that added
them)."""

from __future__ import annotations

from benchmarks import laguna_bytes, peaks
from benchmarks.readers.tracing import module_step_ms
from benchmarks.trace import seconds_matching


def _windows(obs, capture=None) -> list[dict]:
    """The window's decode records that carry both groups' block counts;
    with ``capture``, those that start inside the traced slice."""
    records = [
        r for r in obs['flight']
        if r.get('kind') == 'decode' and 'kv_blocks_full' in r
        and 'kv_blocks_window' in r
    ]
    if capture is None:
        return records
    if capture.t_start is None or capture.t_stop is None:
        return []
    return [
        r for r in records
        if 't0_s' in r and capture.t_start <= r['t0_s'] <= capture.t_stop
    ]


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (``laguna_bytes.decode_step_bytes``:
    held weights once, full-group KV over the rows' contexts, window-group
    KV over what the rows' windows hold; both from the decode records'
    block counts, so rounded up to whole blocks) over the HBM peak, over
    the device time of one step of the decode program."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    windows = _windows(obs)
    if step_ms is None or not windows:
        return None
    bs = ctx.config['engine']['block_size']
    full = bs * sum(r['kv_blocks_full'] for r in windows) / len(windows)
    window = bs * sum(r['kv_blocks_window'] for r in windows) / len(windows)
    bytes_moved = laguna_bytes.decode_step_bytes(ctx.config, full, window)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def paged_attn_bw_share(ctx, obs, pattern: str):
    """KV bytes the decode windows of the traced slice ask the paged
    kernel to read (each record's ``kv_blocks_full`` and
    ``kv_blocks_window`` times the bytes a block holds in the group's
    layers, times ``decode_steps``) over the HBM peak, over the device
    seconds of the kernel's calls in those windows, which ``pattern``
    tells from the prefill programs' calls by their result types."""
    summary = obs['trace']
    if not summary:
        return None
    kernel_s = seconds_matching(summary['op_s'], pattern)
    windows = _windows(obs, ctx.capture)
    if not kernel_s or not windows:
        return None
    engine = ctx.config['engine']
    asked = engine['decode_steps'] * laguna_bytes.kv_bytes(
        ctx.config,
        engine['block_size'] * sum(r['kv_blocks_full'] for r in windows),
        engine['block_size'] * sum(r['kv_blocks_window'] for r in windows),
    )
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * asked / peak_bw / kernel_s


def window_held_share(ctx, obs):
    """Blocks of the windowed group the decode rows hold over the blocks
    their whole contexts fill (the full group's count): what the
    window-aware allocator keeps of what a uniform pool would."""
    windows = _windows(obs)
    full = sum(r['kv_blocks_full'] for r in windows)
    if not full:
        return None
    return 100.0 * sum(r['kv_blocks_window'] for r in windows) / full
