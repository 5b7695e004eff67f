"""Readers of the engine's step spans (``docs/observability.md``
"Serving-path spans"): the ``seq``/``t0_s``/``t1_s`` stamps and phase
seconds on its step records, its ``compile`` records of the serving path,
the ``tokens_lost`` of its ``preempt`` records, the prefill accounting of
its ``request`` records, and the ``distllm:`` host spans of the device
trace.

Each returns None where the program under test writes no such field (a
program from before these spans existed), so that the line leaves the
metric out instead of reporting a zero it did not measure.
"""

from __future__ import annotations

from benchmarks import peaks, reduce
from benchmarks.trace import seconds_matching

_STEP_KINDS = ('prefill', 'decode', 'mixed', 'spec')
# The root of the engine's span tree, open over the whole serving loop: it
# says the engine was serving and not which phase, so it explains no gap.
_ROOT_SPAN = 'distllm:serve'


def _has_spans(obs) -> bool:
    """Whether the window's step records carry the span stamps at all."""
    return any(
        r.get('kind') in _STEP_KINDS and 'seq' in r and 't0_s' in r
        for r in obs['flight']
    )


def _serving_compiles(obs) -> list[dict]:
    return [
        r for r in obs['flight']
        if r.get('kind') == 'compile' and r.get('path') == 'serving'
    ]


def serving_compile_ms(ctx, obs):
    """Milliseconds the host stood in jax's backend compile (or its load
    from the persistent cache) on the serving path, inside the window: the
    sum of ``duration_s`` over the ``compile`` records with ``path ==
    'serving'``. Should read 0."""
    if not _has_spans(obs):
        return None
    return 1e3 * sum(r['duration_s'] for r in _serving_compiles(obs))


def relowered_programs(ctx, obs):
    """Programs compiled inside the window for argument shapes the same
    jit function had already compiled: the serving ``compile`` records
    flagged ``relowered``. Should read 0."""
    if not _has_spans(obs):
        return None
    return float(sum(1 for r in _serving_compiles(obs) if r.get('relowered')))


def reprefill_share(ctx, obs):
    """Share of the tokens prefilled in the window that were prefilled
    again after a preemption: ``tokens_lost`` summed over the ``preempt``
    records, over ``tokens`` summed over the ``prefill`` records."""
    if not _has_spans(obs):
        return None
    prefilled = sum(
        r.get('tokens', 0) for r in obs['flight'] if r.get('kind') == 'prefill'
    )
    if not prefilled:
        return None
    lost = sum(
        sum(r.get('tokens_lost', ()))
        for r in obs['flight'] if r.get('kind') == 'preempt'
    )
    return 100.0 * lost / prefilled


def prefill_wait_p95_ms(ctx, obs):
    """95th percentile, over the window's finished requests, of the time
    between admission and first token that was not spent in the request's
    own prefill steps: waiting out the window in flight, others' prefills
    and chunks. ``prefill_first_s`` counts the prefill steps up to the
    first token only: a later re-prefill after a preemption is no part of
    this wait."""
    values = []
    for r in obs['flight']:
        if r.get('kind') != 'request':
            continue
        admit, first = r.get('t_admit_s'), r.get('t_first_s')
        own = r.get('prefill_first_s')
        if admit is None or first is None or own is None:
            continue
        values.append(max(0.0, first - admit - own))
    return 1e3 * reduce.percentile(values, 0.95) if values else None


def idle_outside_spans_share(ctx, obs):
    """Share of the traced slice (first device op to last) in which the
    device was idle and no phase span of the engine holds the gap: the
    idle gaps the trace reduction gives to a ``bench:`` span, to nothing,
    or to the engine's root span, over ``span_s``. The reduction gives a
    gap to the shortest span that covers half of it, so a gap that runs
    through several phases, none of them half of it, falls to the root.
    What the engine's phase spans do not explain; falls as they do."""
    summary = obs['trace']
    if not summary or not summary['span_s']:
        return None
    outside = sum(
        seconds for name, seconds in summary['gap_s'].items()
        if not name.startswith('distllm:') or name == _ROOT_SPAN
    )
    return 100.0 * outside / summary['span_s']


def decode_kv_bytes_asked(ctx, obs) -> float | None:
    """Bytes of K and V the decode windows that start inside the traced
    slice ask the paged-attention kernel to read: ``kv_blocks`` of each
    ``decode`` record (the blocks that hold its rows' contexts at
    dispatch) times the bytes a block holds over all layers, times the
    ``decode_steps`` steps of the window. A context grows by one token a
    step, so the later steps of a window may read one block more than is
    counted: under 1% at contexts of some hundred tokens."""
    capture = ctx.capture
    if capture.t_start is None or capture.t_stop is None:
        return None
    engine = ctx.config['engine']
    window_bytes = (
        engine['block_size'] * peaks.decoder_kv_bytes_per_token(ctx.config)
        * engine['decode_steps']
    )
    blocks = [
        r['kv_blocks'] for r in obs['flight']
        if r.get('kind') == 'decode' and 'kv_blocks' in r and 't0_s' in r
        and capture.t_start <= r['t0_s'] <= capture.t_stop
    ]
    return float(sum(blocks) * window_bytes) if blocks else None


def paged_attn_bw_share(ctx, obs, pattern: str):
    """KV bytes the decode windows of the traced slice ask the
    paged-attention kernel to read, over the HBM peak, over the device
    seconds of the kernel's calls in those windows: the share of the
    memory roofline the kernel reaches in decode. ``pattern`` has to find
    the decode window's call of the kernel alone (the trace names every
    call ``closed_call``; its result type tells the programs apart), so
    that bytes and seconds cover the same calls."""
    summary = obs['trace']
    if not summary:
        return None
    kernel_s = seconds_matching(summary['op_s'], pattern)
    asked = decode_kv_bytes_asked(ctx, obs)
    if not kernel_s or asked is None:
        return None
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * asked / peak_bw / kernel_s
