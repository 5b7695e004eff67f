"""Readers for a decoder whose every layer holds K/V pages AND recurrent
state (``falcon_h1``): the decode step against its byte account, the state
update against its memory roofline, and the paged kernel's decode calls at 5
queries a KV head against the larger of its two rooflines. Program and scope
find the device seconds (``lfm2.kernel_seconds``, ``hybrid.scope_seconds``),
never a result type. Each reader returns None where the run has nothing to
read: no traced slice, no program or scope of that name (the parent of the
PR that added them), or no decode record with the counter."""

from __future__ import annotations

from benchmarks import falcon_h1_bytes, peaks
from benchmarks.readers.lfm2 import _windows
from benchmarks.readers.tracing import module_step_ms
from benchmarks.trace import seconds_matching


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (``falcon_h1_bytes.
    decode_step_bytes``: the held layers and the head once, the K and V
    pages of the rows' contexts in every layer once, from the decode
    records' block counts, and twice the state of the rows that ran, from
    their ``state_rows``) over the HBM peak, over the device time of one
    step of the decode program: the share of the whole step. The sampler's
    passes are not counted, so this is a floor."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    windows = [r for r in _windows(obs) if 'state_rows' in r]
    if step_ms is None or not windows:
        return None
    engine = ctx.config['engine']
    steps = engine['decode_steps'] * len(windows)
    rows = sum(r['state_rows'] for r in windows) / steps
    tokens = engine['block_size'] * sum(
        r['kv_blocks'] for r in windows
    ) / len(windows)
    bytes_moved = falcon_h1_bytes.decode_step_bytes(ctx.config, rows, tokens)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def ssm_decode_bw_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the state
    update cannot do without (``falcon_h1_bytes.state_update_bytes``: the
    state of the records' ``state_rows`` once read and once written, the
    mixers' weights once a step, over the HBM peak) over the device
    seconds under the scope ``pattern`` finds in ``obs['scope_s']``."""
    scopes = obs.get('scope_s')
    if not scopes:
        return None
    scope_s = seconds_matching(scopes, pattern)
    windows = [r for r in _windows(obs, ctx.capture) if 'state_rows' in r]
    if not scope_s or not windows:
        return None
    steps = ctx.config['engine']['decode_steps'] * len(windows)
    bytes_moved = falcon_h1_bytes.state_update_bytes(
        ctx.config, sum(r['state_rows'] for r in windows), steps
    )
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / scope_s


def paged_attn_roofline_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the paged
    kernel cannot do without, ``max(bytes / HBM peak, operations / bf16
    peak)`` of what the records' ``kv_blocks`` ask of it (a page's bytes
    once for K and once for V in every layer, the operations of all 5
    queries a KV head; times ``decode_steps``), over the device seconds of
    the kernel's calls in the programs and under the scope that ``pattern``
    finds among ``obs['kernel_call_s']``'s ``'<program> <scope>'`` keys."""
    calls = obs.get('kernel_call_s')
    if not calls:
        return None
    kernel_s = seconds_matching(calls, pattern)
    windows = _windows(obs, ctx.capture)
    if not kernel_s or not windows:
        return None
    engine = ctx.config['engine']
    tokens = engine['decode_steps'] * engine['block_size'] * sum(
        r['kv_blocks'] for r in windows
    )
    peak_flops, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    least_s = max(
        falcon_h1_bytes.kv_bytes(ctx.config, tokens) / peak_bw,
        falcon_h1_bytes.attn_flops(ctx.config, tokens) / peak_flops,
    )
    return 100.0 * least_s / kernel_s
