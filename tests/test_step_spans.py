"""Serving-path spans (docs/observability.md "Serving-path spans"): one set
of clock reads behind a step's flight record and its ``distllm:`` profiler
annotations, ``compile`` records for every program compiled on the serving
path, and preemption / prefill routes counted where they happen.

CPU, toy sizes; every test has a time limit of its own.
"""

from __future__ import annotations

import functools
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.models import mistral
from distllm_tpu.observability import (
    CompileWatcher,
    FlightRecorder,
    instruments,
    steps,
)

STEP_KINDS = ('prefill', 'decode', 'mixed', 'spec')
CHILD_FIELDS = ('admit_s', 'host_s', 'put_s', 'dispatch_s', 'fetch_s',
                'emit_s')


def time_limit(seconds: int):
    """Fail the test, and do not hang the worker, after ``seconds``."""

    def wrap(test):
        @functools.wraps(test)
        def limited(*args, **kwargs):
            def on_alarm(signum, frame):
                raise TimeoutError(f'{test.__name__} ran over {seconds} s')

            before = signal.signal(signal.SIGALRM, on_alarm)
            signal.alarm(seconds)
            try:
                return test(*args, **kwargs)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, before)

        return limited

    return wrap


def _engine(**cfg_kwargs) -> LLMEngine:
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )

    class IdTokenizer:
        eos_id = None

    settings = dict(
        block_size=4, num_blocks=64, max_num_seqs=4, max_model_len=64,
        prefer_native_allocator=False,
    )
    settings.update(cfg_kwargs)
    return LLMEngine(
        cfg, mistral.init(jax.random.PRNGKey(0), cfg), IdTokenizer(),
        EngineConfig(**settings),
    )


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 60, n)] for n in lengths]


def _since(engine, recorded_before):
    grew = engine.flight.total_recorded - recorded_before
    return engine.flight.snapshot()[-grew:] if grew else []


# ------------------------------------------------------------- one clock
@time_limit(30)
def test_monotonic_and_perf_counter_are_one_clock():
    """``t0_s``/``t1_s`` are read on ``time.monotonic`` and joined to capture
    windows read on ``time.perf_counter``: on Linux both are
    CLOCK_MONOTONIC, and the helper reads only the one."""
    assert steps.clock is time.monotonic
    mono = time.get_clock_info('monotonic')
    perf = time.get_clock_info('perf_counter')
    assert mono.implementation == perf.implementation
    assert mono.monotonic and perf.monotonic
    gaps = []
    for _ in range(100):
        a = time.monotonic()
        b = time.perf_counter()
        c = time.monotonic()
        assert a <= b <= c
        gaps.append(c - a)
    assert min(gaps) < 1e-4


# ----------------------------------------------------- the helper itself
@time_limit(30)
def test_marks_share_one_clock_read_and_fill_the_record_fields():
    step = steps.StepSpan(seq=7, annotate=False)
    t_plan = step.mark('plan')
    assert steps.current() == ('plan', 7)
    t_put = step.mark('put')
    t_call = step.mark('decode')
    t_paused = step.pause()
    assert steps.current() is None
    t_fetch = step.mark('fetch')
    t_emit = step.mark('emit')
    t_end = step.close()
    fields = step.fields()
    # consecutive spans meet at the one read that closed one and opened the
    # next, so the fields add up to the marks' extent exactly
    assert step.seconds == {
        'host_s': pytest.approx(t_put - t_plan),
        'put_s': pytest.approx(t_call - t_put),
        'dispatch_s': pytest.approx(t_paused - t_call),
        'fetch_s': pytest.approx(t_emit - t_fetch),
        'emit_s': pytest.approx(t_end - t_emit),
    }
    assert fields['seq'] == 7
    assert fields['t0_s'] <= round(t_plan, 6) <= fields['t1_s']
    assert fields['t1_s'] == round(t_end, 6)
    assert set(instruments.STEP_SPANS) >= {
        'admit', 'plan', 'put', 'fetch', 'emit', 'preempt', *STEP_KINDS,
    }


@time_limit(30)
def test_a_step_inside_admit_nests_there_and_plan_keeps_preempt():
    outer = steps.StepSpan(seq=1, annotate=True)  # annotations cost nothing
    outer.mark('admit')
    inner = steps.StepSpan(seq=2, annotate=True)
    inner.mark('plan')
    time.sleep(0.02)
    assert steps.current() == ('plan', 2)
    inner.mark('prefill')
    inner.close()
    assert steps.current() == ('admit', 1)
    outer.mark('plan')
    with outer.inside('preempt'):
        assert steps.current() == ('preempt', 1)
        time.sleep(0.01)
    outer.close()
    assert steps.current() is None
    # the inner step is a record of its own, inside the admit span
    assert outer.seconds['admit_s'] >= inner.seconds['host_s'] >= 0.02
    assert outer.seconds['host_s'] >= outer.seconds['preempt_s'] >= 0.01
    # a raise in mid-step leaves nothing open
    broken = steps.StepSpan(seq=3)
    broken.mark('plan')
    with pytest.raises(RuntimeError):
        with broken.inside('preempt'):
            raise RuntimeError('boom')
    steps.abandon()
    assert steps.current() is None


# ------------------------------------------------- edges another thread reads
def _on_a_fresh_thread(body):
    """Run ``body`` on a thread that has opened no span yet; returns what
    it returns (an assertion that fails there fails the test)."""
    import threading

    out = []

    def run():
        # an ended thread's ident can be handed out again, its edge with it
        steps.forget(threading.get_ident())
        try:
            out.append(('ok', body()))
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            out.append(('raised', exc))

    thread = threading.Thread(target=run, name='span-test-thread')
    thread.start()
    thread.join(20)
    assert out, 'the thread did not end'
    verdict, value = out[0]
    if verdict == 'raised':
        raise value
    return value


def _where(edge):
    return edge.span, edge.seq, edge.root


@time_limit(30)
def test_edge_table_follows_marks_pauses_nesting_abandon_and_a_root():
    """The module's table says, to any thread, where a thread's spans
    stand and since when: every ``mark``/``pause``/``close``/``inside``
    /``abandon`` moves its one entry, with the clock read the span made."""
    import threading

    def body():
        ident = threading.get_ident()
        assert ident not in {e.ident for e in steps.edges()}
        root = steps.StepSpan(seq=100)
        t_root = root.mark('serve')
        edge = next(e for e in steps.edges() if e.ident == ident)
        assert edge.thread == 'span-test-thread'
        assert edge.native_id == threading.get_native_id()
        assert _where(edge) == (None, None, True) and edge.t == t_root
        step = steps.StepSpan(seq=101)
        t_admit = step.mark('admit')
        assert _where(edge) == ('admit', 101, True) and edge.t == t_admit
        # a step inside admit is the innermost; closing it uncovers admit
        inner = steps.StepSpan(seq=102)
        inner.mark('plan')
        assert _where(edge) == ('plan', 102, True)
        t_inner = inner.close()
        assert _where(edge) == ('admit', 101, True) and edge.t == t_inner
        step.mark('plan')
        with step.inside('preempt'):
            assert _where(edge) == ('preempt', 101, True)
        assert _where(edge) == ('plan', 101, True)
        step.mark('decode')
        # paused (the window in flight): a hole under the root, which
        # remembers the step whose span closed last
        t_paused = step.pause()
        assert _where(edge) == (None, 101, True) and edge.t == t_paused
        step.mark('fetch')
        assert _where(edge) == ('fetch', 101, True)
        step.close()
        assert _where(edge) == (None, 101, True)
        # a dispatch that raised midway: abandon closes what it left open
        broken = steps.StepSpan(seq=103)
        broken.mark('plan')
        broken._push('preempt', steps.clock())
        steps.abandon()
        assert _where(edge) == (None, 103, False)  # the root went too
        assert edge.t >= root.t0
        # a span with no root around it (a lone tier span)
        lone = steps.StepSpan(seq=104)
        lone.mark('promote')
        assert _where(edge) == ('promote', 104, False)
        t_end = lone.close()
        assert _where(edge) == (None, 104, False) and edge.t == t_end
        assert edge.flagged is None and edge.carry is None
        return ident

    ident = _on_a_fresh_thread(body)
    assert ident in {e.ident for e in steps.edges()}
    steps.forget(ident)  # what the watcher does for a thread that ended
    assert ident not in {e.ident for e in steps.edges()}


@time_limit(30)
def test_a_hole_under_the_root_is_the_next_records_serve_self_s():
    """``serve_self_s``: the seconds under an open root and under no other
    span since the last record that carried the field. With the children's
    own fields it adds up to the root's extent: nothing of the thread's
    time under a root is in no field."""

    def body():
        root = steps.StepSpan(seq=200)
        root.mark('serve')
        time.sleep(0.02)  # the loop's own lines before the first step
        first = steps.StepSpan(seq=201)
        first.mark('plan')
        time.sleep(0.01)
        first.mark('decode')
        first.pause()
        time.sleep(0.03)  # a hole: the window in flight
        first.mark('fetch')
        first.close()
        one = first.fields()
        time.sleep(0.01)
        second = steps.StepSpan(seq=202)
        second.mark('admit')
        nested = steps.StepSpan(seq=203)  # inside admit: its record is first
        nested.mark('plan')
        time.sleep(0.01)
        nested.close()
        inside = nested.fields()
        second.close()
        two = second.fields()
        time.sleep(0.01)  # behind the last record: left for the next one
        root.close()
        left = steps._local.edge.self_s
        return root, one, inside, two, left

    root, one, inside, two, left = _on_a_fresh_thread(body)
    assert one['serve_self_s'] == pytest.approx(0.05, abs=0.02)
    assert one['serve_self_s'] >= 0.05
    # the next record to be written takes the hole, whichever step's it is
    assert inside['serve_self_s'] == pytest.approx(0.01, abs=0.01)
    assert two['serve_self_s'] == 0.0  # admit was open all the while
    assert left >= 0.01
    children = sum(
        seconds for record in (one, two)  # the nested step's lie in admit_s
        for name, seconds in record.items() if name in CHILD_FIELDS
    )
    selves = one['serve_self_s'] + inside['serve_self_s'] + two['serve_self_s']
    assert children + selves + left == pytest.approx(
        root.t1 - root.t0, abs=1e-3
    )
    assert 'stalled_s' not in one and 'stalled_s' not in two


@time_limit(30)
def test_attribution_off_writes_no_edge():
    import threading

    def body():
        root = steps.StepSpan(seq=300, annotate=False)
        root.mark('serve')
        step = steps.StepSpan(seq=301, annotate=False)
        step.mark('plan')
        assert steps.current() == ('plan', 301)
        step.close()
        root.close()
        assert 'serve_self_s' not in step.fields()
        assert not hasattr(steps._local, 'edge')
        ident = threading.get_ident()
        assert ident not in {e.ident for e in steps.edges()}

    _on_a_fresh_thread(body)


@time_limit(60)
def test_an_engine_with_attribution_off_is_not_watched_and_writes_no_field():
    from distllm_tpu.observability.flight import get_stall_watchdog

    def body():
        engine = _engine(attribution=False)
        assert engine not in get_stall_watchdog()._engines
        before = engine.flight.total_recorded
        engine.generate_ids(
            _prompts((5,)), SamplingParams(temperature=0.0, max_tokens=4)
        )
        records = [
            r for r in _since(engine, before) if r['kind'] in STEP_KINDS
        ]
        assert records
        assert not any('serve_self_s' in r or 'seq' in r for r in records)
        assert not hasattr(steps._local, 'edge')
        engine.shutdown()

    _on_a_fresh_thread(body)


@time_limit(120)
def test_engine_records_partition_the_serving_threads_time_under_the_root():
    """Over one pipelined call, the step records' top-level fields and
    their ``serve_self_s`` add up to the call's root span, which is open
    from the loop's start to its end."""
    engine = _engine()
    engine.generate_ids(  # compiles: a call of its own
        _prompts((5, 9)), SamplingParams(temperature=0.0, max_tokens=9)
    )
    edge = steps._local.edge
    assert engine in get_watched()
    before = engine.flight.total_recorded
    edge.self_s = 0.0
    t0 = steps.clock()
    engine.generate_ids(
        _prompts((5, 9, 17)), SamplingParams(temperature=0.0, max_tokens=9)
    )
    t1 = steps.clock()
    records = [r for r in _since(engine, before) if r['kind'] in STEP_KINDS]
    assert all('serve_self_s' in r for r in records)
    windows = [r for r in records if r['kind'] != 'prefill']
    under_root = sum(
        r.get(f, 0.0) for r in windows for f in CHILD_FIELDS
    ) + sum(r['serve_self_s'] for r in records) + edge.self_s
    # a window's admit_s holds its prefill steps; what the call spends
    # outside the root (building requests, collecting outputs) is the rest
    assert under_root <= t1 - t0 + 1e-3
    assert under_root >= 0.8 * (t1 - t0)
    stat = engine.stall_context()
    assert stat['unfinished'] == 0 and stat['in_flight'] == 0
    assert stat['ready'] == [] and stat['compiling'] is False
    import threading

    assert stat['thread'] == threading.get_ident()
    engine.shutdown()
    assert engine not in get_watched()


def get_watched():
    from distllm_tpu.observability.flight import get_stall_watchdog

    return set(get_stall_watchdog()._engines)


# -------------------------------------------------------- step records
def _check_step_records(records):
    step_records = [r for r in records if r['kind'] in STEP_KINDS]
    assert step_records
    seqs = [r['seq'] for r in step_records]
    assert len(set(seqs)) == len(seqs)
    by_start = sorted(step_records, key=lambda r: r['t0_s'])
    assert [r['seq'] for r in by_start] == sorted(seqs)
    for r in step_records:
        assert r['t0_s'] <= r['t1_s']
        children = sum(r.get(f, 0.0) for f in CHILD_FIELDS)
        # each field is rounded to a microsecond
        assert children <= r['t1_s'] - r['t0_s'] + 1e-5, r
        assert {'host_s', 'put_s', 'dispatch_s'} <= set(r)
        if r['kind'] == 'prefill':
            assert r['route'] in ('dense', 'paged', 'chunk')
            assert 'admit_s' not in r
        else:
            assert {'admit_s', 'fetch_s', 'emit_s', 'kv_blocks'} <= set(r)
            assert r['kv_blocks'] >= r['batch']
    return step_records


@time_limit(120)
def test_pipelined_loop_stamps_every_step_record():
    engine = _engine()
    before = engine.flight.total_recorded
    engine.generate_ids(
        _prompts((5, 9, 17, 30, 12, 7)),
        SamplingParams(temperature=0.0, max_tokens=20),
    )
    step_records = _check_step_records(_since(engine, before))
    kinds = {r['kind'] for r in step_records}
    assert kinds == {'prefill', 'decode'}
    # a window record's clock stamps bracket its admission's prefill steps
    windows = [r for r in step_records if r['kind'] == 'decode']
    assert any(
        w['t0_s'] <= p['t0_s'] and p['t1_s'] <= w['t1_s']
        for w in windows for p in step_records if p['kind'] == 'prefill'
    )
    engine.shutdown()


@time_limit(120)
def test_step_loop_stamps_every_step_record_and_attribution_off_sheds_them():
    engine = _engine()
    before = engine.flight.total_recorded
    for prompt in _prompts((6, 11)):
        engine.add_request(prompt, SamplingParams(temperature=0.0, max_tokens=9))
    while engine.has_unfinished:
        engine.step()
    records = _since(engine, before)
    _check_step_records(records)
    for r in records:
        if r['kind'] == 'request':
            assert r['t_admit_s'] <= r['t_first_s']
            assert r['prefill_first_s'] > 0
    engine.attribution = False
    before = engine.flight.total_recorded
    engine.add_request(_prompts((6,))[0], SamplingParams(max_tokens=9))
    while engine.has_unfinished:
        engine.step()
    for r in _since(engine, before):
        if r['kind'] in STEP_KINDS:
            assert not {'seq', 't0_s', 'host_s', 'emit_s'} & set(r)
    assert steps.current() is None
    engine.shutdown()


@time_limit(120)
@pytest.mark.parametrize('temperatures,expected', [
    ((0.0, 0.0, 0.0), {0}),  # the sampler's argmax branch, every dispatch
    ((0.7, 0.7, 0.7), {1, 2, 3}),
    ((0.0, 0.7, 0.0), {0, 1}),
])
def test_sampled_rows_counts_what_the_sampler_cond_sees(temperatures, expected):
    """``sampled_rows`` is the dispatch's rows with temperature > 0, on
    decode and on sampling prefill records alike (0 = the dispatch ran
    ``argmax`` only); it never exceeds the rows the dispatch carried."""
    engine = _engine()
    before = engine.flight.total_recorded
    for prompt, t in zip(_prompts((6, 11, 8)), temperatures):
        engine.add_request(
            prompt, SamplingParams(temperature=t, top_p=0.9, max_tokens=9)
        )
    while engine.has_unfinished:
        engine.step()
    records = [
        r for r in _since(engine, before) if r['kind'] in ('prefill', 'decode')
    ]
    assert {r['kind'] for r in records} == {'prefill', 'decode'}
    assert all('sampled_rows' in r for r in records)
    assert {r['sampled_rows'] for r in records} <= expected
    assert max(r['sampled_rows'] for r in records) == max(expected)
    decodes = [r for r in records if r['kind'] == 'decode']
    assert all(r['sampled_rows'] <= r['running'] for r in decodes)
    if all(t > 0 for t in temperatures):
        assert all(r['sampled_rows'] == r['batch'] for r in decodes)
    engine.shutdown()


# ------------------------------------------- preemption and prefill routes
@time_limit(180)
def test_small_pool_preempts_and_every_lost_token_is_counted():
    """Invariant C: over a drained engine the tokens of the prefill records
    equal the prompt tokens less the cached tokens of first admission
    (none here: the prompts share no block) plus the tokens lost to
    preemption."""
    engine = _engine(
        num_blocks=24, enable_prefix_cache=True, prefill_chunk_tokens=16,
        decode_steps=4,
    )
    # Admission looks ahead to the end of every budget and would not let
    # this pool run short. Preemption is the net under a look-ahead whose
    # estimate was low, so teach it one: an answer that stops within 2
    # tokens of a budget of 40. The walk then looks one window ahead
    # (4 tokens), and these requests run to 16. (To 24 the victims, which
    # keep their prompt blocks while they wait, pin this pool until a
    # lone running row exhausts it: PERF.md section 7.)
    probe = _prompts((6,), seed=1)
    stop = _engine().generate_ids(
        probe, SamplingParams(temperature=0.0, max_tokens=2)
    )[0][1]
    engine.generate_ids(probe, SamplingParams(
        temperature=0.0, max_tokens=40, stop_token_ids=[stop]
    ))
    assert engine._ewma['budget_use'] <= 2 / 40
    before = engine.flight.total_recorded
    prompts = _prompts((10, 20, 30, 12, 25, 18))
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=16)
    )
    assert all(len(o) == 16 for o in outputs)
    records = _since(engine, before)
    prefills = [r for r in records if r['kind'] == 'prefill']
    preempts = [r for r in records if r['kind'] == 'preempt']
    requests = [r for r in records if r['kind'] == 'request']
    assert preempts, 'the pool was meant to be too small'
    assert len(requests) == len(prompts)

    lost = sum(sum(r['tokens_lost']) for r in preempts)
    prompt_tokens = sum(len(p) for p in prompts)
    prefilled = sum(r['tokens'] for r in prefills)
    assert lost > 0
    assert prefilled == prompt_tokens + lost
    # the same sum, request by request
    assert sum(r['prefill_tokens'] for r in requests) == prefilled
    for r in preempts:
        assert len(r['rids']) == len(r['tokens_lost'])
        assert all(n > 0 for n in r['tokens_lost'])
        assert isinstance(r['seq'], int)
    victims = [rid for r in preempts for rid in r['rids']]
    by_rid = {r['request_id']: r for r in requests}
    for rid, record in by_rid.items():
        assert record['preemptions'] == victims.count(rid)
        lost_here = sum(
            n for r in preempts
            for victim, n in zip(r['rids'], r['tokens_lost']) if victim == rid
        )
        assert record['prefill_tokens'] == record['prompt_tokens'] + lost_here
        if record['preemptions']:
            # the prefix cache keeps full blocks: it comes back by the
            # paged route, behind its cached blocks
            assert record['routes'].get('paged', 0) >= 1
        else:
            assert record['cached_tokens'] == 0
    routes = {r['route'] for r in prefills}
    assert routes == {'dense', 'chunk', 'paged'}
    # routes on the records and on the requests agree
    for route in routes:
        assert sum(r['routes'].get(route, 0) for r in requests) == sum(
            r['batch'] for r in prefills if r['route'] == route
        )
    engine.shutdown()


@time_limit(120)
def test_cached_tokens_of_first_admission_are_not_prefilled():
    """Invariant C's other term: a prompt behind cached blocks prefills its
    tail only, by the paged route."""
    engine = _engine(enable_prefix_cache=True)
    params = SamplingParams(temperature=0.0, max_tokens=4)
    shared = _prompts((40,))[0]
    engine.generate_ids([shared], params)
    before = engine.flight.total_recorded
    prompt = shared[:32] + _prompts((9,))[0]
    engine.generate_ids([prompt], params)
    records = _since(engine, before)
    (request,) = [r for r in records if r['kind'] == 'request']
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert request['cached_tokens'] == 32 and request['preemptions'] == 0
    assert sum(r['tokens'] for r in prefills) == len(prompt) - 32
    assert request['prefill_tokens'] == len(prompt) - 32
    assert request['routes'] == {'paged': 1}
    engine.shutdown()


# ---------------------------------------------------- compile records
@time_limit(180)
def test_serving_compiles_are_records_and_a_repeat_compiles_nothing():
    engine = _engine()  # no warmup: the first request compiles in serving
    params = SamplingParams(temperature=0.0, max_tokens=10)
    prompt = _prompts((9,))[0]
    before = engine.flight.total_recorded
    engine.generate_ids([prompt], params)
    first = [r for r in _since(engine, before) if r['kind'] == 'compile']
    programs = {r['program'] for r in first}
    # (a module-level function such as _write_prefill_all_layers shares
    # jax's cache with every engine of the process, so it may be warm)
    assert {'jit(prefill_fn)', 'jit(window_fn)'} <= programs
    step_seqs = {
        r['seq'] for r in _since(engine, before) if r['kind'] in STEP_KINDS
    }
    for r in first:
        assert r['path'] == 'serving' and r['duration_s'] > 0
        assert r['cache_hit'] in (True, False)
        assert r['during'] in instruments.STEP_SPANS, r
        assert r['seq'] in step_seqs
    by_program = {r['program']: r for r in first}
    assert by_program['jit(prefill_fn)']['during'] == 'prefill'
    assert by_program['jit(window_fn)']['during'] == 'decode'
    # compiled inside one of the engine's own jit calls: signature kept
    assert by_program['jit(window_fn)']['relowered'] is False
    # the step that paid for the compile says so in its dispatch_s
    window = next(
        r for r in _since(engine, before)
        if r['kind'] == 'decode'
        and r['seq'] == by_program['jit(window_fn)']['seq']
    )
    assert window['dispatch_s'] >= by_program['jit(window_fn)']['duration_s']

    before = engine.flight.total_recorded
    engine.generate_ids([list(reversed(prompt))], params)
    again = _since(engine, before)
    assert [r for r in again if r['kind'] == 'compile'] == []
    assert [r for r in again if r['kind'] == 'decode']
    engine.shutdown()


@time_limit(60)
def test_relowering_names_the_argument_that_changed():
    """The same shapes, one argument committed to its device and then not:
    jax lowers the program again, and the record says which argument."""
    recorder = FlightRecorder()
    watch = CompileWatcher(recorder=recorder).listen()
    try:
        fn = jax.jit(lambda table, rows: table * 2 + rows)
        rows = jnp.ones((3,))
        LLMEngine._call(fn, jnp.ones((4, 3)), rows)
        LLMEngine._call(fn, jnp.ones((4, 3)), rows)  # steady: no compile
        committed = jax.device_put(jnp.ones((4, 3)), jax.devices()[0])
        LLMEngine._call(fn, committed, rows)
        LLMEngine._call(fn, jnp.ones((5, 3)), rows)  # other shapes: new
    finally:
        watch.unlisten()
    ours = [
        r for r in recorder.snapshot()
        if r['kind'] == 'compile' and r['program'] == 'jit(<lambda>)'
    ]
    assert [r['relowered'] for r in ours] == [False, True, False]
    (change,) = ours[1]['changed']
    assert change == {
        'arg': 'table', 'what': 'committed', 'was': 'False', 'now': 'True',
    }
    assert steps.call_in_flight() is None


@time_limit(60)
def test_call_signature_hashes_a_parameter_tree_and_reads_each_array():
    from distllm_tpu.observability.startup import call_signature

    def fn(params, ids, *rest):
        return ids

    tree = {'a': jnp.ones((2, 3)), 'b': [jnp.zeros((4,), jnp.int32)]}
    sig = call_signature(fn, (tree, jnp.ones((5,), jnp.int32), 3, None))
    assert list(sig) == ['params', 'ids', 'rest', 'arg3']
    kind, leaves, shapes_hash, whole_hash = sig['params']
    assert (kind, leaves) == ('tree', 2)
    shape, dtype, weak, committed, sharding, layout = sig['ids']
    assert (shape, dtype, weak, committed) == ((5,), 'int32', False, False)
    other = call_signature(
        fn, ({'a': jnp.ones((2, 3)), 'b': [jnp.zeros((4,), jnp.float32)]},)
    )
    assert other['params'][2] == shapes_hash  # same shapes
    assert other['params'][3] != whole_hash   # another dtype
