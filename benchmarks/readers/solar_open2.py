"""Readers for a decoder with Kimi-delta layers beside a paged attention
layer (``solar_open2``): the decode step against its byte account, the two
forms of the delta rule against their rooflines (the span form of prefill,
the step update of decode; projections left out of both), and the paged
kernel's decode calls at 8 queries a KV head against the larger of its two
rooflines. Program and scope find the device seconds
(``lfm2.kernel_seconds``, ``hybrid.scope_seconds``), never a result type.
Each reader returns None where the run has nothing to read: no traced slice,
no program or scope of that name (the parent of the PR that added them), or
no step record with the counter."""

from __future__ import annotations

from benchmarks import peaks, solar_open2_bytes
from benchmarks.readers.lfm2 import _windows
from benchmarks.readers.tracing import module_step_ms
from benchmarks.trace import seconds_matching


def _prefills(obs, capture) -> list[dict]:
    """The ``prefill`` records of the paged route (``paged`` or ``chunk``:
    every prefill of a model with a state pool, each through the span
    form) that start inside the traced slice."""
    if capture.t_start is None or capture.t_stop is None:
        return []
    return [
        r for r in obs['flight']
        if r.get('kind') == 'prefill' and r.get('route') in ('paged', 'chunk')
        and 't0_s' in r
        and capture.t_start <= r['t0_s'] <= capture.t_stop
    ]


def _scope_s(obs, pattern: str):
    scopes = obs.get('scope_s')
    return seconds_matching(scopes, pattern) if scopes else None


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (``solar_open2_bytes.
    decode_step_bytes``: the held layers and the head once, the K and V
    pages of the rows' contexts in the attention layer once, from the
    decode records' block counts, and twice the state of the rows that ran,
    from their ``state_rows``) over the HBM peak, over the device time of
    one step of the decode program: the share of the whole step. The
    sampler's passes are not counted, so this is a floor."""
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
    bytes_moved = solar_open2_bytes.decode_step_bytes(ctx.config, rows, tokens)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def kda_span_roofline_share(ctx, obs, pattern: str):
    """For the prefill dispatches of the traced slice: the seconds the span
    recurrence cannot do without, ``max(operations / bf16 peak, bytes / HBM
    peak)`` of the records' counted ``tokens`` and rows (``solar_open2_bytes.
    kda_span_flops``, ``kda_span_bytes``: the recurrence's own work a token,
    its inputs and output once, each row's matrix state read and written
    once a span), over the device seconds under the scope ``pattern`` finds
    in ``obs['scope_s']``."""
    scope_s = _scope_s(obs, pattern)
    spans = _prefills(obs, ctx.capture)
    if not scope_s or not spans:
        return None
    tokens = sum(r['tokens'] for r in spans)
    rows = sum(r['batch'] for r in spans)
    peak_flops, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    least_s = max(
        solar_open2_bytes.kda_span_flops(ctx.config, tokens) / peak_flops,
        solar_open2_bytes.kda_span_bytes(ctx.config, tokens, rows) / peak_bw,
    )
    return 100.0 * least_s / scope_s


def kda_step_bw_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the step
    update cannot do without (``solar_open2_bytes.kda_step_bytes``: the
    float32 matrix state of the records' ``state_rows`` once read and once
    written in every KDA layer, over the HBM peak) over the device seconds
    under the scope ``pattern`` finds in ``obs['scope_s']``."""
    scope_s = _scope_s(obs, pattern)
    windows = [r for r in _windows(obs, ctx.capture) if 'state_rows' in r]
    if not scope_s or not windows:
        return None
    bytes_moved = solar_open2_bytes.kda_step_bytes(
        ctx.config, sum(r['state_rows'] for r in windows)
    )
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / scope_s


def paged_attn_roofline_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the paged
    kernel cannot do without, ``max(bytes / HBM peak, operations / bf16
    peak)`` of what the records' ``kv_blocks`` ask of it (a page's bytes
    once for K and once for V in the attention layer, the operations of all
    8 queries a KV head; times ``decode_steps``), over the device seconds of
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
        solar_open2_bytes.kv_bytes(ctx.config, tokens) / peak_bw,
        solar_open2_bytes.attn_flops(ctx.config, tokens) / peak_flops,
    )
    return 100.0 * least_s / kernel_s
