"""Readers for a decoder with one latent cache group (``deepseek_v3`` with
latent attention): the decode step against its byte account, and the paged
kernel's decode calls against the larger of its two rooflines. Each returns
None where the run has nothing to read: no traced slice, no program of that
name (the parent of the PR that added it), or no decode record with
``kv_blocks``."""

from __future__ import annotations

from benchmarks import deepseek_v3_bytes, peaks
from benchmarks.readers.tracing import module_step_ms
from benchmarks.trace import seconds_matching


def _windows(obs, capture=None) -> list[dict]:
    """The window's decode records that carry their rows' block count; with
    ``capture``, those that start inside the traced slice."""
    records = [
        r for r in obs['flight']
        if r.get('kind') == 'decode' and 'kv_blocks' in r
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
    """Least bytes a decode step moves (``deepseek_v3_bytes.
    decode_step_bytes``: held weights once, the stored latent rows of the
    rows' contexts once, from the decode records' block counts, so rounded
    up to whole blocks) over the HBM peak, over the device time of one step
    of the decode program."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    windows = _windows(obs)
    if step_ms is None or not windows:
        return None
    tokens = ctx.config['engine']['block_size'] * sum(
        r['kv_blocks'] for r in windows
    ) / len(windows)
    bytes_moved = deepseek_v3_bytes.decode_step_bytes(ctx.config, tokens)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def latent_attn_roofline_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the paged
    kernel cannot do without, ``max(bytes / HBM peak, operations / bf16
    peak)`` of what the records' ``kv_blocks`` ask of it (stored rows once,
    keys and values being one row; times ``decode_steps``), over the device
    seconds of the kernel's calls in those windows, which ``pattern`` tells
    from the prefill programs' calls by their result types."""
    summary = obs['trace']
    if not summary:
        return None
    kernel_s = seconds_matching(summary['op_s'], pattern)
    windows = _windows(obs, ctx.capture)
    if not kernel_s or not windows:
        return None
    engine = ctx.config['engine']
    tokens = engine['decode_steps'] * engine['block_size'] * sum(
        r['kv_blocks'] for r in windows
    )
    peak_flops, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    least_s = max(
        deepseek_v3_bytes.latent_bytes(ctx.config, tokens) / peak_bw,
        deepseek_v3_bytes.attn_flops(ctx.config, tokens) / peak_flops,
    )
    return 100.0 * least_s / kernel_s
