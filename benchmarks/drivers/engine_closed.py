"""Closed loop over the serving engine's offline API: call after call of
``engine.generate_ids`` (the pipelined loop that ``distributed_generation``
reaches through ``TpuGenerator.generate``), each with ``prompts_per_call``
fresh prompts, so that the queue is empty only while a call drains. The
window ends at the end of the call in flight.

Traffic parameters: ``prompts_per_call``, ``prompt_tokens`` (a size spec),
``output_tokens`` (``fixed``: one budget for the whole call). Every call has
the same sizes in the same order (the cell's) and other token ids, so every
call makes the engine dispatch the same programs: admission, batching and
preemption follow from the lengths and the KV pool alone. The warm-up is
one such call (``warmup`` ``replica_calls``, 1 unless the cell says more) with
token ids of its own, so that whatever a call of the window meets has run
before it, the re-admission of preempted requests included; a list of shapes
missed programs that only that re-admission makes (``PERF.md`` section 6).
"""

from __future__ import annotations

import time

import jax

from benchmarks import traffic
from benchmarks.drivers import _engine


def _call_prompts(ctx, stream: str) -> list[list[int]]:
    spec = ctx.traffic
    batch = traffic.requests(
        spec, int(spec['prompts_per_call']), ctx.config['vocab_size'],
        ctx.seed, stream, order_stream='call',
    )
    return [list(r.prompt_ids) for r in batch]


def _call_summary(flight: list[dict], start: float, end: float) -> dict:
    """What one call did, from the engine's flight records between its two
    wall-clock times: seconds, steps and tokens by kind, the three slowest
    steps with the engine's own split of each, and the longest time between
    two records. Calls of one cell do the same work, so a slow call whose
    counts match the others was stalled, and the split says where."""
    steps = [
        r for r in flight
        if start <= r.get('t_wall', 0.0) <= end and 'duration_s' in r
    ]
    by_kind: dict[str, list] = {}
    for r in steps:
        entry = by_kind.setdefault(r['kind'], [0, 0])
        entry[0] += 1
        entry[1] += int(r.get('tokens', 0))
    times = [start, *(r['t_wall'] for r in steps), end]
    split = ('kind', 'duration_s', 'batch', 'tokens', 'host_s', 'put_s',
             'dispatch_s', 'fetch_s')
    slowest = sorted(steps, key=lambda r: -r['duration_s'])[:3]
    return {
        's': end - start,
        'steps_tokens': by_kind,
        'slowest': [{k: r[k] for k in split if k in r} for r in slowest],
        'longest_gap_s': max(b - a for a, b in zip(times, times[1:])),
    }


def prepare(ctx) -> dict:
    state = _engine.build(ctx)
    engine = state['engine']
    budget = int(ctx.traffic['output_tokens']['value'])
    for n in range(int(ctx.workload.get('warmup', {}).get('replica_calls', 1))):
        engine.generate_ids(
            _call_prompts(ctx, f'warmup{n}'), _engine.sampling(ctx, budget)
        )
    state['excluded_s'] = _engine.sample_for_check(state, ctx)
    return state


def measure(state, ctx) -> dict:
    engine = state['engine']
    budget = int(ctx.traffic['output_tokens']['value'])
    params = _engine.sampling(ctx, budget)
    tokens = attempted = failed = 0
    prompt_tokens = 0
    calls, walls = [], []
    recorded_before = engine.flight.total_recorded
    ctx.capture.arm()
    t0 = time.perf_counter()
    while True:
        prompts = _call_prompts(ctx, f'call{len(calls)}')
        # A trace covers whole calls: it starts before one and stops after.
        ctx.capture.poll()
        t_call = time.perf_counter() - t0
        wall_start = time.time()
        with jax.profiler.TraceAnnotation('bench:generate_ids'):
            outputs = engine.generate_ids(prompts, params)
        now = time.perf_counter() - t0
        calls.append((t_call, now))
        walls.append((wall_start, time.time()))
        attempted += len(prompts)
        prompt_tokens += sum(len(p) for p in prompts)
        for out in outputs:
            tokens += len(out)
            failed += not 0 < len(out) <= budget
        over = now >= ctx.seconds
        ctx.capture.poll(last=over)
        if over:
            break
    window_s = time.perf_counter() - t0
    flight = _engine.flight_since(engine, recorded_before)
    return {
        'end_to_end': {'gen_tok_s': tokens / window_s},
        'attempted': attempted,
        'failed': failed,
        'window_s': window_s,
        'counters': {
            'calls': calls,
            'output_tokens': tokens,
            # A sequence's context while it decodes: its prompt and, on
            # average, half of its output.
            'mean_context_tokens': prompt_tokens / attempted + budget / 2,
        },
        'detail': {'calls': [_call_summary(flight, *wall) for wall in walls]},
        'flight': flight,
    }


verify = _engine.verify
close = _engine.close
