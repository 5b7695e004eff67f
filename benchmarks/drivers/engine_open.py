"""Open loop over the serving engine: requests arrive on a schedule fixed by
the cell (a Poisson process at ``rate_rps``), whether or not the engine keeps
up; one thread submits what is due and then calls ``engine.step()``. Every
request due in the window is sent; the run drains them after the window, for
at most ``drain_limit_s``, and times them all.

The per-request clock is the benchmark's own: scheduled arrival, first token
seen in what ``step()`` returns, last token seen. A request still unfinished
at the end counts as failed and enters both percentiles as the time the run
waited for it.

Adapted from ``distllm_tpu/generate/loadgen.py run_loadgen``, which reads the
engine's private request table for the same times.

Traffic parameters: ``rate_rps``, ``prompt_tokens``, ``output_tokens`` (size
specs), ``shared_prefix`` (optional), ``drain_limit_s``.
"""

from __future__ import annotations

import time

from benchmarks import reduce, traffic
from benchmarks.drivers import _engine


def _drive(engine, requests, params_of, on_step=None, limit_s=float('inf')):
    """Submit ``requests`` at their offsets and step the engine until all
    have finished or ``limit_s`` has passed. Returns one record per request
    and the end time (seconds from the start)."""
    order = sorted(range(len(requests)), key=lambda i: requests[i].at_s)
    records = [
        {'scheduled': r.at_s, 'submitted': None, 'first': None, 'last': None,
         'tokens': 0, 'budget': r.max_tokens}
        for r in requests
    ]
    by_rid: dict[int, dict] = {}
    live = 0
    next_i = 0
    t0 = time.perf_counter()
    while next_i < len(order) or live:
        now = time.perf_counter() - t0
        if now > limit_s:
            break
        while next_i < len(order) and requests[order[next_i]].at_s <= now:
            i = order[next_i]
            next_i += 1
            rid = engine.add_request(
                list(requests[i].prompt_ids), params_of(requests[i])
            )
            records[i]['submitted'] = time.perf_counter() - t0
            by_rid[rid] = records[i]
            live += 1
        if live:
            emitted = engine.step()
            seen = time.perf_counter() - t0
            for rid, _token in emitted:
                rec = by_rid[rid]
                if rec['first'] is None:
                    rec['first'] = seen
                rec['last'] = seen
                rec['tokens'] += 1
                if rec['tokens'] == rec['budget']:
                    live -= 1
            if on_step is not None:
                on_step(seen)
        else:
            wait = requests[order[next_i]].at_s - now
            time.sleep(min(0.002, max(0.0, wait)))
    return records, time.perf_counter() - t0


def prepare(ctx) -> dict:
    state = _engine.build(ctx)
    engine = state['engine']
    for prompts, budget in _engine.warmup_calls(ctx):
        batch = [traffic.Request(0.0, tuple(p), budget) for p in prompts]
        _drive(engine, batch, lambda r: _engine.sampling(ctx, r.max_tokens))
    state['excluded_s'] = _engine.sample_for_check(state, ctx)
    return state


def measure(state, ctx) -> dict:
    engine = state['engine']
    spec = ctx.traffic
    arrivals = traffic.poisson_arrivals(
        float(spec['rate_rps']), ctx.seconds, traffic.schedule_rng(spec, 'arrivals')
    )
    requests = traffic.requests(
        spec, len(arrivals), ctx.config['vocab_size'], ctx.seed, 'requests',
        arrivals=arrivals,
    )
    recorded_before = engine.flight.total_recorded
    ctx.capture.arm()

    def on_step(seen: float) -> None:
        ctx.capture.poll(last=seen >= ctx.seconds)

    records, end_s = _drive(
        engine, requests, lambda r: _engine.sampling(ctx, r.max_tokens),
        on_step=on_step, limit_s=ctx.seconds + float(spec['drain_limit_s']),
    )
    ctx.capture.poll(last=True)
    ttft, tpot, lag = [], [], []
    failed = 0
    for rec in records:
        done = rec['tokens'] == rec['budget']
        failed += not done
        # An unfinished request enters as the time the run waited for it.
        first = rec['first'] if rec['first'] is not None else end_s
        last = rec['last'] if done else end_s
        ttft.append(first - rec['scheduled'])
        tpot.append((last - first) / max(rec['tokens'] - 1, 1)
                    if rec['first'] is not None else end_s - rec['scheduled'])
        if rec['submitted'] is not None:
            lag.append(rec['submitted'] - rec['scheduled'])
    waiting_mid = sum(
        1 for r in records
        if r['scheduled'] <= ctx.seconds / 2
        and (r['last'] is None or r['tokens'] < r['budget'] or r['last'] > ctx.seconds / 2)
    )
    waiting_end = sum(
        1 for r in records
        if r['tokens'] < r['budget'] or r['last'] > ctx.seconds
    )
    return {
        'end_to_end': {
            'ttft_p95_ms': 1e3 * reduce.percentile(ttft, 0.95),
            'tpot_p95_ms': 1e3 * reduce.percentile(tpot, 0.95),
        },
        'attempted': len(records),
        'failed': failed,
        'window_s': end_s,
        'counters': {
            'ttft_s': ttft,
            'tpot_s': tpot,
            'lag_s': lag,
            'output_tokens': sum(r['tokens'] for r in records),
            # Requests in the system at the window's middle and at its end:
            # more at the end than in the middle is a growing backlog.
            'in_system_mid': waiting_mid,
            'in_system_end': waiting_end,
            'drain_s': end_s - ctx.seconds,
        },
        'flight': _engine.flight_since(engine, recorded_before),
    }


verify = _engine.verify
close = _engine.close
