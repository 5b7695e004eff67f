"""Readers for a looped decoder (``ouro``: the stack run ``total_ut_steps``
times a token, a K/V plane a layer a pass): the decode step against its byte
account, the paged kernel's decode calls at one query a KV head against the
larger of its two rooflines and as a share of the device's busy time, and
the share of decoded tokens whose head read the last pass. The kernel's
calls are found by the PROGRAM they run in and the SCOPE they run under
(``lfm2.kernel_seconds``), never by a result type. Each reader returns None
where the run has nothing to read: no traced slice, no program or scope of
that name, or no decode record with the counter (the parent of the PR that
added them)."""

from __future__ import annotations

from benchmarks import ouro_bytes, peaks
from benchmarks.readers.lfm2 import _windows
from benchmarks.readers.tracing import module_step_ms
from benchmarks.trace import seconds_matching


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (``ouro_bytes.decode_step_bytes``:
    the stack's weights once a PASS, the head once, the K and V rows of the
    rows' contexts in every plane, from the decode records' block counts,
    and the rows written) over the HBM peak, over the device time of one
    step of the decode program: the share of the whole step. The sampler's
    passes are not counted, so this is a floor."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    windows = _windows(obs)
    if step_ms is None or not windows:
        return None
    rows = sum(r['batch'] for r in windows) / len(windows)
    tokens = ctx.config['engine']['block_size'] * sum(
        r['kv_blocks'] for r in windows
    ) / len(windows)
    bytes_moved = ouro_bytes.decode_step_bytes(ctx.config, rows, tokens)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def _kernel_seconds(obs, pattern: str):
    calls = obs.get('kernel_call_s')
    return seconds_matching(calls, pattern) if calls else None


def paged_attn_time_share(ctx, obs, pattern: str):
    """Share of the device's busy time in the paged kernel's calls that
    ``pattern`` finds among ``obs['kernel_call_s']``'s ``'<program>
    <scope>'`` keys."""
    kernel_s, summary = _kernel_seconds(obs, pattern), obs.get('trace')
    if not kernel_s or not summary or not summary['busy_s']:
        return None
    return 100.0 * kernel_s / summary['busy_s']


def paged_attn_roofline_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the paged
    kernel cannot do without, ``max(bytes / HBM peak, operations / bf16
    peak)`` of what the records' ``kv_blocks`` ask of it (a page's bytes
    once for K and once for V in every plane, the operations of every query
    head; times ``decode_steps``), over the device seconds of the kernel's
    calls in the programs and under the scope that ``pattern`` finds."""
    kernel_s = _kernel_seconds(obs, pattern)
    windows = _windows(obs, ctx.capture)
    if not kernel_s or not windows:
        return None
    engine = ctx.config['engine']
    tokens = engine['decode_steps'] * engine['block_size'] * sum(
        r['kv_blocks'] for r in windows
    )
    peak_flops, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    least_s = max(
        ouro_bytes.kv_bytes(ctx.config, tokens) / peak_bw,
        ouro_bytes.attn_flops(ctx.config, tokens) / peak_flops,
    )
    return 100.0 * least_s / kernel_s


def last_pass_share(ctx, obs):
    """Percent of the window's decoded tokens whose head read the LAST pass,
    from the decode records' ``loop_exit_pass`` (the tokens of live rows by
    the pass the exit gate chose): 100 at the published threshold of 1, and
    a change that moves it has changed the model."""
    counts = [
        r['loop_exit_pass'] for r in obs['flight']
        if r.get('kind') == 'decode' and 'loop_exit_pass' in r
    ]
    total = sum(sum(c) for c in counts)
    if not total:
        return None
    return 100.0 * sum(c[-1] for c in counts) / total
