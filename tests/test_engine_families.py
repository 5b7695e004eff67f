"""What ``LLMEngine`` owes every family it serves, written once and run over
each family's toy at tiny widths on the CPU (the models themselves:
``test_<family>.py``): greedy generation against the plain reference's
logits, teacher-forced; what a finished request left in its slot and pages;
rows that end in windows of their own; more prompts than slots; a slot
reused after a longer holder; a preempted request admitted again; sampled
generation and its records; the settings and the mesh the family refuses, by
name; warm-up, then serving. (That no line of the engine names a family:
``test_family_scaffold.py``.)

A new family adds its toy and its row: ``<family>_toy.py`` holds
``make_engine``, ``prompt``, ``token_gap`` (its reference, teacher-forced)
and ``ENGINE_CASES``, the cases it takes with their shapes and the checks
that are its own (what its records carry, what "left" compares). A case a
family does not list is not run for it. What only one family has stays in
``test_<family>_engine.py``.

Cases that leave an engine as they found it take it from ``engines``, one a
module for each (family, settings); cases that fill or starve one build
their own.

Under ``--dist loadfile`` a file is one worker's chain, so the suite runs as
four: this file over ``FAMILIES``, and ``test_engine_families_2.py``,
``_3.py`` and ``_4.py``, which import every case from here and name other families
(``pytest_generate_tests`` reads the collecting module's ``FAMILIES``). A new
family's name goes where the chain is shortest (a chain none of whose
families lists a case reports that case once, as skipped).
"""

import importlib

import jax
import numpy as np
import pytest

from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)

FAMILIES = ('granite', 'ouro')
TOYS = {
    name: importlib.import_module(f'{name}_toy') for name in
    ('granite', 'lfm2', 'falcon_h1', 'solar_open2', 'ouro', 'smallthinker',
     'sdar')
}
GREEDY = dict(temperature=0.0)
REFUSED = {
    'enable_prefix_cache': dict(enable_prefix_cache=True),
    'host_kv_tier_bytes': dict(enable_prefix_cache=True, host_kv_tier_bytes=1 << 20),
    'enable_mixed_batching': dict(enable_mixed_batching=True),
    'draft_k': dict(draft_k=2),
    'kv_cache_dtype=int8': dict(kv_cache_dtype='int8'),
    'quantization': dict(quantization='int8'),
}


def case(name):
    """The test runs over the families whose row lists ``name``; one that
    takes ``entry`` runs over every entry of it."""
    def mark(test):
        test.case = name
        return test

    return mark


def pytest_generate_tests(metafunc):
    if 'family' not in metafunc.fixturenames:
        return
    name = getattr(metafunc.function, 'case', None)
    rows = {f: TOYS[f].ENGINE_CASES for f in metafunc.module.FAMILIES}
    if 'entry' not in metafunc.fixturenames:
        metafunc.parametrize('family', [f for f in rows if name is None or name in rows[f]])
        return

    def ident(entry):  # a setting's name, or a greedy case's lengths and backend
        return entry if isinstance(entry, str) else (
            f"{'+'.join(map(str, entry[1]))}-{entry[2]}"
        )

    metafunc.parametrize('family, entry', [
        pytest.param(f, entry, id=f'{f}-{ident(entry)}')
        for f in rows for entry in rows[f].get(name, ())
    ])


@pytest.fixture(scope='module')
def engines():
    held = {}

    def get(family, hf_over=None, **over):
        key = family, repr(hf_over), repr(sorted(over.items()))
        if key not in held:
            held[key] = TOYS[family].make_engine(hf_over=hf_over, **over)
        return held[key]

    yield get
    held.clear()


def assert_teacher_forced(family, hf, params, prompts, outputs, limit=1e-3):
    """Every generated token is the reference's greedy token given the same
    history, or within ``limit`` standard deviations of it (float32 on both
    sides: a tie is the one way to differ)."""
    for p, o in zip(prompts, outputs):
        ids = np.asarray([list(p) + list(o)[:-1]])
        at = len(p) - 1 + np.arange(len(o))[None]
        assert TOYS[family].token_gap(params, hf, ids, at, list(o)) < limit


def serve(family, engine, seed, lengths, **sampling):
    """``(prompts, outputs, records)`` of one ``generate_ids``."""
    rng = np.random.default_rng(seed)
    prompts = [TOYS[family].prompt(rng, n) for n in lengths]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(prompts, SamplingParams(**sampling))
    assert [len(o) for o in outputs] == [sampling['max_tokens']] * len(prompts)
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    return prompts, outputs, records


def finished(family, engine, seed, lengths, max_tokens):
    """``(tokens fed, request record)`` of each request of a greedy call."""
    prompts, outputs, records = serve(
        family, engine, seed, lengths, max_tokens=max_tokens, **GREEDY
    )
    requests = sorted(
        (r for r in records if r['kind'] == 'request'), key=lambda r: r['request_id']
    )
    assert len(requests) == len(prompts)
    return [(list(p) + list(o)[:-1], r) for p, o, r in zip(prompts, outputs, requests)]


@case('greedy')
def test_greedy_tokens_are_the_references(engines, family, entry):
    seed, lengths, backend = entry
    cases = TOYS[family].ENGINE_CASES
    hf, params, engine = engines(
        family, **({} if backend == 'xla' else {'attn_backend': backend})
    )
    prompts, outputs, records = serve(
        family, engine, seed, lengths, max_tokens=cases.get('greedy_tokens', 7), **GREEDY
    )
    assert_teacher_forced(family, hf, params, prompts, outputs)
    cases['after_greedy'](engine, params, records, lengths, backend)


@case('left')
def test_what_a_finished_request_left_is_the_references(engines, family):
    """What the benchmark's content limits read: the ``request`` record
    names the slot and the first and last block a request held, and the
    pools keep what they held."""
    left = TOYS[family].ENGINE_CASES['left']
    hf, params, engine = engines(family)
    requests = finished(
        family, engine, left['seed'], left['lengths'], left['max_tokens']
    )
    if engine.state_pool is not None:
        assert sorted(r['state_slot'] for _, r in requests) == [0, 1, 2]
    for fed, record in requests:
        left['check'](engine, hf, params, fed, record)


@case('windows')
def test_rows_of_different_lengths_finish_at_different_windows(engines, family):
    seed, rows = TOYS[family].ENGINE_CASES['windows']
    hf, params, engine = engines(family)
    rng = np.random.default_rng(seed)
    prompts = [TOYS[family].prompt(rng, n) for n, _ in rows]
    got = {
        engine.add_request(p, SamplingParams(max_tokens=m, **GREEDY)): []
        for p, (_, m) in zip(prompts, rows)
    }
    while engine.has_unfinished:
        for rid, token in engine.step():
            got[rid].append(token)
    outputs = list(got.values())
    assert [len(o) for o in outputs] == [m for _, m in rows]
    assert_teacher_forced(family, hf, params, prompts, outputs)


@case('turnover')
def test_more_prompts_than_slots_turn_every_slot_over(engines, family):
    hf, params, engine = engines(family)
    prompts, outputs, _ = serve(
        family, engine, 1, (5, 19, 11, 30, 7, 3, 14, 9, 2), max_tokens=10, **GREEDY
    )
    assert_teacher_forced(family, hf, params, prompts, outputs)


@case('reuse')
def test_a_slot_reused_after_a_longer_holder_starts_from_zero(family):
    toy = TOYS[family]
    hf, params, engine = toy.make_engine(max_num_seqs=1)
    rng = np.random.default_rng(2)
    sampling = SamplingParams(max_tokens=6, **GREEDY)
    engine.generate_ids([toy.prompt(rng, 17)], sampling)
    # The one slot now holds the first request's state; the next request
    # takes it, alone and after a call that left the pipeline empty.
    for n in toy.ENGINE_CASES['reuse']:
        later = toy.prompt(rng, n)
        out = engine.generate_ids([later], sampling)
        assert_teacher_forced(family, hf, params, [later], out)


@case('preempt')
def test_a_preempted_request_is_admitted_again_from_nothing(engines, family):
    """Two rows whose prompts and budgets need more blocks than the pool
    has: one is preempted, admitted again, and both read as the
    reference's (and, where the row says ``roomy``, as the tokens of an
    engine whose pool never ran short)."""
    from distllm_tpu.observability import instruments

    toy = TOYS[family]
    case = toy.ENGINE_CASES['preempt']
    hf, params, engine = toy.make_engine(num_blocks=case['num_blocks'], max_num_seqs=2)
    # As if finished requests had used none of their budgets: the
    # look-ahead then admits both rows, and the pool runs short under them.
    engine._ewma['budget_use'] = 0.0
    before = instruments.SCHED_PREEMPTIONS.value
    prompts, outputs, _ = serve(
        family, engine, case['seed'], (case['n'],) * 2, max_tokens=20, **GREEDY
    )
    assert instruments.SCHED_PREEMPTIONS.value > before
    assert_teacher_forced(family, hf, params, prompts, outputs)
    if engine.window_blocks is not None:
        assert engine.window_blocks.num_held == 0
    if case.get('roomy'):
        roomy = engines(family, max_num_seqs=2)[2]
        assert roomy.generate_ids(
            prompts, SamplingParams(max_tokens=20, **GREEDY)
        ) == outputs


@case('sampled')
def test_sampled_generation_and_its_records(engines, family):
    case = TOYS[family].ENGINE_CASES['sampled']
    engine = engines(family, hf_over=case.get('hf_over'))[2]
    _, _, records = serve(
        family, engine, case['seed'], case['lengths'], **case['sampling']
    )
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert prefills and all(r['route'] in ('paged', 'chunk') for r in prefills)
    case.get('check', lambda engine, records: None)(engine, records)


@case('refused')
def test_the_family_refuses_by_name_what_it_cannot_serve(family, entry):
    # a tier needs the cache: either's refusal may be the first
    named = '(host_kv_tier_bytes|enable_prefix_cache)' if 'tier' in entry else entry
    message = f"{named} {TOYS[family].ENGINE_CASES['refusal']}"
    with pytest.raises(ValueError, match=message):
        TOYS[family].make_engine(**REFUSED[entry])


def test_the_family_refuses_a_mesh(family):
    from jax.sharding import Mesh

    toy = TOYS[family]
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = toy.tiny(0)
    with pytest.raises(ValueError, match=f"mesh {toy.ENGINE_CASES['refusal']}"):
        LLMEngine(
            cfg, params, toy.NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


def test_warmup_compiles_every_shape_and_serves_after(family):
    toy = TOYS[family]
    hf, params, engine = toy.make_engine(max_model_len=32, max_num_seqs=2)
    engine.warmup()
    p = toy.prompt(np.random.default_rng(6), toy.ENGINE_CASES['warm_prompt'])
    out = engine.generate_ids([p], SamplingParams(max_tokens=5, **GREEDY))
    assert_teacher_forced(family, hf, params, [p], out)
