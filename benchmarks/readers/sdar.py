"""Readers for ``sdar_moe`` (generation by diffusion over blocks): a forward
of the decode window against its byte account, the paged kernel's decode
calls (a block folded into the group: 32 queries a KV head through the row
walk) against the larger of its two rooflines, and the forwards a decided
position cost, from the counters the window's program adds up. Each returns
None where the run has nothing to read: no traced slice, no program of that
name, or records that lack the counters (the parent of the PR that added
them)."""

from __future__ import annotations

from benchmarks import peaks, sdar_bytes
from benchmarks.trace import seconds_matching


def _windows(obs, capture=None) -> list[dict]:
    """The window's decode records that carry the block counters; with
    ``capture``, those that start inside the traced slice."""
    records = [
        r for r in obs['flight']
        if r.get('kind') == 'decode' and 'forwards' in r and 'kv_blocks' in r
    ]
    if capture is None:
        return records
    if capture.t_start is None or capture.t_stop is None:
        return []
    return [
        r for r in records
        if 't0_s' in r and capture.t_start <= r['t0_s'] <= capture.t_stop
    ]


def forward_ms(ctx, obs, pattern: str):
    """Device milliseconds a forward of the programs the pattern finds:
    their device time over runs times the forwards a window makes (blocks a
    window x denoise steps and the commit)."""
    summary = obs['trace']
    if not summary:
        return None
    runs = seconds_matching(summary['module_n'], pattern)
    if not runs or 'denoise_steps' not in ctx.config['engine']:
        return None
    forwards = sdar_bytes.forwards_a_window(ctx.config)
    return 1e3 * seconds_matching(summary['module_s'], pattern) / (runs * forwards)


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a forward moves (``sdar_bytes.forward_bytes``: held
    weights once, the head in the denoise forwards, the K and V of the rows'
    contexts from the decode records' block counts, the block's rows
    written) over the HBM peak, over the device time of one forward of the
    decode program: the share of the whole forward."""
    step_ms = forward_ms(ctx, obs, pattern)
    windows = [r for r in _windows(obs) if 'batch' in r]
    if step_ms is None or not windows:
        return None
    block = ctx.config['engine']['block_size']
    tokens = block * sum(r['kv_blocks'] for r in windows) / len(windows)
    rows = sum(r['batch'] for r in windows) / len(windows)
    bytes_moved = sdar_bytes.forward_bytes(ctx.config, tokens, rows)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def paged_attn_roofline_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the paged
    kernel cannot do without, ``max(bytes / HBM peak, operations / bf16
    peak)`` of what the records' ``kv_blocks`` ask of it in every layer,
    times the forwards a window makes, over the device seconds of the
    kernel's calls in the programs and under the scopes that ``pattern``
    finds among ``obs['kernel_call_s']``'s ``'<program> <scope>'`` keys."""
    calls = obs.get('kernel_call_s')
    if not calls:
        return None
    kernel_s = seconds_matching(calls, pattern)
    windows = _windows(obs, ctx.capture)
    if not kernel_s or not windows:
        return None
    forwards = sdar_bytes.forwards_a_window(ctx.config)
    tokens = forwards * ctx.config['engine']['block_size'] * sum(
        r['kv_blocks'] for r in windows
    )
    peak_flops, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    least_s = max(
        sdar_bytes.kv_bytes(ctx.config, tokens) / peak_bw,
        sdar_bytes.attn_flops(ctx.config, tokens) / peak_flops,
    )
    return 100.0 * least_s / kernel_s


def forwards_per_token(ctx, obs):
    """``forwards / decided`` summed over the window's decode records: a
    live row's block through one forward, over the positions decided in
    those blocks (``S + 1`` forwards a block of ``B``: 1.25 at 4 and 4; a
    little more where a first block was given part of its positions)."""
    windows = _windows(obs)
    decided = sum(r.get('decided', 0) for r in windows)
    if not decided:
        return None
    return sum(r['forwards'] for r in windows) / decided
