"""Chunked prefill in lockstep rounds (``LLMEngine._run_prefill_chunked``):
the long prompts of one admission round are prefilled chunk by chunk
together, a round's spans grouped by ``(bucket, final)``, and a group goes
as one dispatch only when it is full (``_prefill_batch_cap(bucket)`` spans);
what is left goes one span a dispatch.

Toy float32 engines of both families on the CPU: the dense decoder
(``models/mistral.py``) and the hybrid (``models/granite_hybrid.py``, whose
rows also carry a slot of the state pool from chunk to chunk). Chunks of 8
tokens and ``max_prefill_tokens`` 16, so that two spans of bucket 8 fill a
group and four of bucket 4 do. Every check is one of counts or of token
identity; every engine test has a time limit of its own.
"""

from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

from distllm_tpu.generate.engine.engine import SamplingParams
from distllm_tpu.models.tokenizer import pick_bucket
from distllm_tpu.resilience.faults import get_fault_injector

from granite_toy import make_engine as _granite_engine
from test_step_spans import _engine as _mistral_engine
from test_step_spans import _prompts, _since, time_limit

CHUNK = 8
GREEDY = SamplingParams(temperature=0.0, max_tokens=6)
SETTINGS = dict(
    block_size=4, num_blocks=128, max_num_seqs=8, max_model_len=64,
    prefill_min_bucket=4, prefill_chunk_tokens=CHUNK, max_prefill_tokens=16,
    decode_steps=4, attribution=True, attn_backend='xla',
    enable_prefix_cache=False, prefer_native_allocator=False,
    max_dispatch_retries=3, retry_backoff_s=0.0,
)
# The cell's shape in small: eight prompts admitted at once, seven of them
# longer than a chunk (so more than the cap of 2), of two and three chunks,
# with last chunks in both buckets.
LENGTHS = (20, 21, 22, 19, 12, 13, 11, 5)


def _build(family: str):
    if family == 'dense':
        return _mistral_engine(**SETTINGS)
    return _granite_engine(**SETTINGS)[2]


@pytest.fixture(scope='module', params=['dense', 'hybrid'])
def engine(request):
    built = _build(request.param)
    yield built
    built.shutdown()


@pytest.fixture
def injector():
    found = get_fault_injector()
    found.disarm()
    yield found
    found.disarm()


def _rule(engine, lengths) -> Counter:
    """``(bucket, final, rows)`` of every chunk dispatch that one admission
    round of prompts of these lengths makes, counted from the lengths by the
    rule alone."""
    out: Counter = Counter()
    left = [n for n in lengths if n > CHUNK]
    while left:
        spans = Counter(
            (pick_bucket(min(CHUNK, n), engine.prefill_buckets), n <= CHUNK)
            for n in left
        )
        for (bucket, final), count in spans.items():
            cap = engine._prefill_batch_cap(bucket)
            if cap > 1:
                out[bucket, final, cap] += count // cap
                count %= cap
            out[bucket, final, 1] += count
        left = [n - CHUNK for n in left if n > CHUNK]
    return +out


def _call(engine, prompts):
    """One ``generate_ids`` call: its outputs, its ``prefill`` records, and
    each request id's prompt length (ids are given in the prompts' order)."""
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(prompts, GREEDY)
    records = _since(engine, before)
    requests = sorted(
        (r for r in records if r['kind'] == 'request'),
        key=lambda r: r['request_id'],
    )
    assert len(requests) == len(prompts)
    lengths = {r['request_id']: len(p) for r, p in zip(requests, prompts)}
    prefills = [r for r in records if r['kind'] == 'prefill']
    return outputs, prefills, lengths, requests


def _states_left(engine, requests):
    """The SSM state that each request of a call left in its slot of a
    hybrid's state pool, from the call's ``request`` records: a slot keeps
    it until its next holder's first span."""
    slots = [r['state_slot'] for r in requests]
    assert len(set(slots)) == len(slots)
    return [
        [np.asarray(leaf[slot]) for leaf in engine.state_pool.state['ssm']]
        for slot in slots
    ]


def _spans_of(engine, chunks, lengths):
    """Each ``chunk`` record's spans, rebuilt from its ``rids`` and the
    order of the records: ``[(rid, chunk index, tokens, bucket, final)]``."""
    seen: Counter = Counter()
    rebuilt = []
    for record in chunks:
        spans = []
        for rid in record['rids']:
            k = seen[rid]
            seen[rid] += 1
            ntok = min(CHUNK, lengths[rid] - k * CHUNK)
            spans.append((
                rid, k, ntok, pick_bucket(ntok, engine.prefill_buckets),
                (k + 1) * CHUNK >= lengths[rid],
            ))
        assert sum(s[2] for s in spans) == record['tokens']
        rebuilt.append(spans)
    return rebuilt


# (a) and (c): the records of one call against the count made by the rule.
@time_limit(240)
def test_full_groups_go_together_and_the_rest_alone(engine):
    assert engine._prefill_batch_cap(8) == 2
    assert engine._prefill_batch_cap(4) == 4
    prompts = _prompts(LENGTHS, seed=1)
    outputs, prefills, lengths, _ = _call(engine, prompts)
    assert all(len(out) == GREEDY.max_tokens for out in outputs)
    # every prompt token prefilled once
    assert sum(r['tokens'] for r in prefills) == sum(LENGTHS)
    chunks = [r for r in prefills if r['route'] == 'chunk']
    spans = _spans_of(engine, chunks, lengths)
    seen = Counter()
    for record, row in zip(chunks, spans):
        # (c) one bucket and one kind (final or not) a dispatch, and rows
        # that are distinct requests
        assert len({s[3] for s in row}) == 1 and len({s[4] for s in row}) == 1
        assert len({s[0] for s in row}) == len(row) == record['batch']
        bucket, final = row[0][3], row[0][4]
        # full groups or one row, no other count
        assert record['batch'] in (1, engine._prefill_batch_cap(bucket))
        seen[bucket, final, record['batch']] += 1
    expected = _rule(engine, LENGTHS)
    assert seen == expected
    assert len(chunks) == sum(expected.values())
    # the shape of this call, written out: 7 first chunks are 3 groups and
    # one alone; the four prompts of three chunks make 2 more groups; the
    # last chunks of bucket 4 come two a round, short of a group of 4
    assert expected[8, False, 2] == 5 and expected[8, False, 1] == 1
    assert expected[8, True, 2] == 1 and expected[8, True, 1] == 1
    assert expected[4, True, 1] == 4 and expected[4, True, 4] == 0
    # a chunk of every request in order, each of a request's once
    for rid, n in lengths.items():
        ks = [s[1] for row in spans for s in row if s[0] == rid]
        assert ks == list(range(-(-n // CHUNK) if n > CHUNK else 0))
    groups = sum(1 for r in chunks if r['batch'] > 1)
    assert engine.telemetry['prefill_chunk_groups'] == groups == 6
    assert engine.telemetry['prefill_chunks'] == sum(r['batch'] for r in chunks)


# (b): a row of a group computes what the row alone computes. In the hybrid
# a group's rows hold distinct slots of the state pool, and each row's
# second chunk starts from the state its own first chunk left.
@time_limit(240)
def test_grouped_prompts_generate_what_each_generates_alone(engine):
    prompts = _prompts(LENGTHS, seed=2)
    together, prefills, _, requests = _call(engine, prompts)
    assert any(r['route'] == 'chunk' and r['batch'] > 1 for r in prefills)
    hybrid = engine.state_pool is not None
    states = _states_left(engine, requests) if hybrid else []
    for i, (prompt, tokens) in enumerate(zip(prompts, together)):
        alone, own, _, request = _call(engine, [prompt])
        assert all(r['batch'] == 1 for r in own)
        assert alone == [tokens], len(prompt)
        if hybrid:
            # The toy's greedy tokens mostly repeat; its states do not.
            for got, want in zip(states[i], _states_left(engine, request)[0]):
                np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


# (d): the pipelined loop's hook runs after every dispatch that is not
# final and after no final one. ``step()`` leaves the hook alone, so a spy
# put there sees the calls without a decode window in flight.
@time_limit(240)
def test_drain_hook_follows_every_dispatch_that_is_not_final(engine, monkeypatch):
    events = []
    dispatch = engine._dispatch_prefill_paged

    def spy(spans, bucket, defer_to=None, sample=True, route='paged'):
        if route == 'chunk':
            events.append(('final' if sample else 'part', len(spans)))
        return dispatch(spans, bucket, defer_to, sample=sample, route=route)

    monkeypatch.setattr(engine, '_dispatch_prefill_paged', spy)
    monkeypatch.setattr(engine, '_drain_hook', lambda: events.append('drain'))
    for prompt in _prompts(LENGTHS, seed=3):
        engine.add_request(prompt, GREEDY)
    while engine.has_unfinished:
        engine.step()
    parts = [i for i, e in enumerate(events) if e != 'drain' and e[0] == 'part']
    finals = [i for i, e in enumerate(events) if e != 'drain' and e[0] == 'final']
    assert len(parts) == 6 and len(finals) == 6  # the rule's counts, as above
    # the round's remainder goes before its full groups: what follows a
    # decode window in a served stream is the one-row program
    assert events[0] == ('part', 1) and events[2] == ('part', 2)
    assert all(events[i + 1] == 'drain' for i in parts)
    assert all(events[i + 1: i + 2] != ['drain'] for i in finals)
    assert events.count('drain') == len(parts)


# (e): a fault in the dispatch of a group in the second round.
@time_limit(240)
def test_fault_in_a_second_round_group_marks_its_requests(engine, injector, monkeypatch):
    prompts = _prompts(LENGTHS, seed=4)
    clean, prefills, lengths, _ = _call(engine, prompts)
    chunks = [r for r in prefills if r['route'] == 'chunk']
    spans = _spans_of(engine, chunks, lengths)
    # The first group of second chunks: its place among the call's prefill
    # dispatches is the number of visits to the fault site before it.
    target = next(
        record for record, row in zip(chunks, spans)
        if record['batch'] > 1 and row[0][1] == 1
    )
    position = {rid: i for i, rid in enumerate(sorted(lengths))}
    group = [position[rid] for rid in target['rids']]
    marked: list[int] = []
    mark = engine._mark_prefill_retry

    def spy(requests):
        marked.extend(r.request_id for r in requests)
        return mark(requests)

    monkeypatch.setattr(engine, '_mark_prefill_retry', spy)
    injector.arm('dispatch', times=1, after=prefills.index(target))
    before = engine.flight.total_recorded
    again = engine.generate_ids(prompts, GREEDY)
    records = _since(engine, before)
    assert injector.fired('dispatch') == 1
    assert again == clean  # every output, and the clean call's tokens
    assert engine._stats['recoveries'] >= 1
    assert not engine._stats.get('quarantined_requests')
    assert not engine._pending_prefill
    rids = sorted(r['request_id'] for r in records if r['kind'] == 'request')
    faulted = {rids[i] for i in group}
    assert len(faulted) == target['batch'] == 2
    assert faulted <= set(marked)
    # so is every other request whose prefill was part done, and none that
    # had its first token already
    requests = {r['request_id']: r for r in records if r['kind'] == 'request'}
    for rid, record in requests.items():
        again_prefilled = record['prefill_tokens'] > record['prompt_tokens']
        assert again_prefilled == (rid in marked), record
