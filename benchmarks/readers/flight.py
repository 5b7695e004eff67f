"""Readers of the engine's flight records (``engine.flight``, the host-side
per-step records the engine keeps when ``attribution`` is on) that fell
inside the measured window."""

from __future__ import annotations

from benchmarks import reduce

_WINDOW_KINDS = ('decode', 'mixed', 'spec')


def window_host_ms(ctx, obs):
    """Median host time to plan a decode window and put its operands on the
    device (``host_s + put_s``)."""
    values = [
        r['host_s'] + r['put_s'] for r in obs['flight']
        if r.get('kind') in _WINDOW_KINDS and 'host_s' in r and 'put_s' in r
    ]
    return 1e3 * reduce.median(values) if values else None


def decode_occupancy(ctx, obs):
    """Mean share of the ``max_num_seqs`` slots that held a sequence, over
    the window's decode dispatches."""
    values = [
        r['occupancy'] for r in obs['flight']
        if r.get('kind') in _WINDOW_KINDS and 'occupancy' in r
    ]
    return 100.0 * sum(values) / len(values) if values else None


def queue_wait_p95_ms(ctx, obs):
    """95th percentile of the engine's own enqueue-to-admission wait, from
    its ``request`` records. The engine's clock starts at ``add_request``, so
    the generator's lag is not in it."""
    values = [
        r['queue_wait_s'] for r in obs['flight']
        if r.get('kind') == 'request' and r.get('queue_wait_s') is not None
    ]
    return 1e3 * reduce.percentile(values, 0.95) if values else None
