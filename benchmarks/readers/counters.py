"""Readers of what the drivers count themselves. A reader takes the run's
context and observations and returns one number, or None where there is
nothing to read (the harness then leaves the metric out)."""

from __future__ import annotations

from benchmarks import reduce


def compiles_in_window(ctx, obs):
    """Programs compiled, or loaded from the compile cache, inside the
    measured window. Should read 0."""
    return float(obs['compiles_in_window'])


def padding_share(ctx, obs):
    """Share of the token slots sent to the device that were padding."""
    counters = obs['counters']
    if not counters.get('tokens_padded'):
        return None
    return 100.0 * (1.0 - counters['tokens_real'] / counters['tokens_padded'])


def p95_ms(ctx, obs, series: str):
    """95th percentile of one of the driver's per-request series, which
    are kept in seconds."""
    values = obs['counters'].get(series)
    if not values:
        return None
    return 1e3 * reduce.percentile(values, 0.95)
