"""Startup & compile attribution, measured XLA cost, and the bounded
profiler capture (ISSUE 11 tentpole + satellites): one ``compile`` flight
record per warmup shape with cache-hit marking on re-warmup, the Perfetto
startup track, measured-vs-analytic MFU gauges from ``cost_analysis()``,
``startup.json`` in debug bundles, and capture error-safety. ISSUE 36: a
program's stages and clock stamps, nested phases under ``engine_init``,
and the account (``summary``) that outlives the ring and the engine."""

from __future__ import annotations

import json
import threading
import time

import jax
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
    ProfilerCapture,
    dump_debug_bundle,
    get_registry,
    instruments,
    record_backend_init,
    to_trace_events,
    validate_trace_events,
)
from distllm_tpu.observability import startup, steps
from distllm_tpu.observability.perfetto import _STARTUP_TID


def _tiny_engine(max_model_len=64, **cfg_kwargs):
    cfg = mistral.MistralConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=64,
        dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class IdTokenizer:
        eos_id = None

        def decode(self, ids):
            return ' '.join(str(i) for i in ids)

    engine = LLMEngine(
        cfg,
        params,
        IdTokenizer(),
        EngineConfig(
            block_size=4,
            num_blocks=64,
            max_num_seqs=4,
            max_model_len=max_model_len,
            prefer_native_allocator=False,
            **cfg_kwargs,
        ),
    )
    # Isolate from the process-global watcher: other tests warm the same
    # tiny shapes, and process-level dedup would mark them cache hits.
    recorder = FlightRecorder()
    engine._compile_watcher = CompileWatcher(recorder=recorder)
    return engine, recorder


def _compile_records(recorder):
    return [r for r in recorder.snapshot() if r['kind'] == 'compile']


# ------------------------------------------------- warmup instrumentation
def test_warmup_emits_one_compile_record_per_shape():
    engine, recorder = _tiny_engine()
    engine.warmup()
    records = _compile_records(recorder)
    # The exact ladder: every (batch, bucket) prefill the admission
    # policy can emit — buckets (16, 32, 64) x batch (1, 2, 4) — plus
    # the fused decode window. No prefix cache / chunking / mixed / spec
    # in this config, so nothing else may appear.
    prefill = [r for r in records if r['phase'] == 'prefill']
    decode = [r for r in records if r['phase'] == 'decode_window']
    assert len(prefill) == 9 and len(decode) == 1
    assert len(records) == 10
    assert {r['shape'] for r in prefill} == {
        f'b{b}x{bucket}' for bucket in (16, 32, 64) for b in (1, 2, 4)
    }
    assert decode[0]['shape'] == 'b4x8'  # max_num_seqs x decode_steps
    # One record per shape, none marked as a cache hit on a cold watcher,
    # every duration real.
    assert len({(r['phase'], r['shape']) for r in records}) == len(records)
    assert all(not r['cache_hit'] for r in records)
    assert all(r['duration_s'] > 0 for r in records)
    # Timestamps are monotonic: the ladder is sequential, and the
    # Perfetto startup track depends on the ordering.
    stamps = [r['t_wall'] for r in records]
    assert stamps == sorted(stamps)


def test_rewarmup_marks_cache_hit_fast_path():
    engine, recorder = _tiny_engine()
    engine.warmup()
    cold = _compile_records(recorder)
    engine.warmup()
    warm = _compile_records(recorder)[len(cold):]
    assert len(warm) == len(cold)
    assert all(r['cache_hit'] for r in warm)
    # The fast path is actually fast: jit re-dispatch, not re-compile.
    assert sum(r['duration_s'] for r in warm) < sum(
        r['duration_s'] for r in cold
    )


def test_warmup_ladder_includes_paged_shapes_when_chunking():
    engine, recorder = _tiny_engine(max_model_len=32, prefill_chunk_tokens=16)
    engine.warmup()
    records = _compile_records(recorder)
    prefill = {r['shape'] for r in records if r['phase'] == 'prefill'}
    paged = {r['shape'] for r in records if r['phase'] == 'prefill_paged'}
    assert paged == prefill  # every prefill shape has its paged twin


def test_warmup_renders_as_perfetto_startup_track():
    engine, recorder = _tiny_engine()
    engine.warmup()
    doc = to_trace_events(recorder.snapshot())
    assert validate_trace_events(doc) == []
    startup = [
        e for e in doc['traceEvents'] if e.get('cat') == 'startup'
    ]
    assert len(startup) == len(_compile_records(recorder))
    # One dedicated track, named slices like 'prefill:b1x16', phase
    # fields surviving as args.
    assert {e['tid'] for e in startup} == {_STARTUP_TID}
    names = {e['name'] for e in startup}
    assert 'prefill:b1x16' in names and 'decode_window:b4x8' in names
    assert all(e['args']['cache_hit'] is False for e in startup)
    track_names = {
        e['args']['name'] for e in doc['traceEvents']
        if e['ph'] == 'M' and e['name'] == 'thread_name'
    }
    assert 'startup (compile phases)' in track_names


# --------------------------------------------------- watcher semantics
def test_compile_watcher_failure_records_error_not_hit():
    recorder = FlightRecorder()
    watch = CompileWatcher(recorder=recorder)
    with pytest.raises(RuntimeError, match='boom'):
        with watch.phase('prefill', 'b1x16'):
            raise RuntimeError('boom')
    (record,) = _compile_records(recorder)
    assert 'boom' in record['error']
    assert not record['cache_hit']
    # A failed phase must not poison the dedup set: the retry is a real
    # compile, not a "hit".
    with watch.phase('prefill', 'b1x16'):
        pass
    retry = _compile_records(recorder)[-1]
    assert 'error' not in retry and not retry['cache_hit']
    assert watch.state()['active'] is None


def test_compile_watcher_names_the_phase_in_progress():
    """The r03/r04 failure-mode fix: a bundle dumped mid-phase names the
    exact (kind, shape) the process is stuck in."""
    watch = CompileWatcher(recorder=FlightRecorder())
    with watch.phase('decode_window', 'b32x16') as fields:
        fields['note'] = 'wedged here'
        (active,) = watch.state()['active']
        assert active['phase'] == 'decode_window'
        assert active['shape'] == 'b32x16'
        assert active['t_start_wall'] <= time.time()
    assert watch.state()['active'] is None
    assert watch.state()['phases'][-1]['note'] == 'wedged here'


def test_open_phases_are_a_stack_innermost_last():
    """``engine_init`` holds the engine's own phases: a bundle dumped in
    one of them says ``engine_init > auto_layout``, an inner phase's exit
    leaves the outer one open, and a program names the innermost phase
    while every open phase counts it."""
    recorder = FlightRecorder()
    watch = CompileWatcher(recorder=recorder).listen()
    try:
        with watch.phase('engine_init', 'mistral:b4'):
            with watch.phase('auto_layout', 'b4'):
                assert [p['phase'] for p in watch.state()['active']] == [
                    'engine_init', 'auto_layout',
                ]
                _jax_compiles('jit(window_fn)', from_cache=True)
            assert [p['phase'] for p in watch.state()['active']] == [
                'engine_init'
            ]
            _jax_compiles('jit(merge)', from_cache=False)
        assert watch.state()['active'] is None
    finally:
        watch.unlisten()
    inner_program, inner, outer_program, outer = _compile_records(recorder)
    assert (inner_program['phase'], inner_program['path']) == (
        'auto_layout', 'startup'
    )
    assert (outer_program['phase'], outer_program['shape']) == (
        'engine_init', 'mistral:b4'
    )
    assert (inner['programs'], inner['cache_hits']) == (1, 1)
    assert (outer['programs'], outer['cache_hits']) == (2, 1)
    assert inner['cache_hit'] and not outer['cache_hit']
    # the inner phase lies inside the outer one on the step records' clock
    assert outer['t0_s'] <= inner['t0_s'] <= inner['t1_s'] <= outer['t1_s']


def _jax_traces(program: str, seconds: float) -> None:
    from jax import monitoring

    monitoring.record_event_duration_secs(
        '/jax/core/compile/jaxpr_trace_duration', seconds, fun_name=program
    )


def _jax_compiles(program: str, *, from_cache: bool, seconds=0.01,
                  written=False, trace_s=(), lower_s=None) -> None:
    """What jax reports when it compiles ``program`` (or loads it from the
    persistent cache): its own monitoring events, in its own order.
    ``written``: the compile was asked of the cache, missed and was written
    to it. ``trace_s``: one trace event each; ``lower_s``: the lowering."""
    from jax import monitoring

    for traced in trace_s:
        _jax_traces(program, traced)
    if lower_s is not None:
        monitoring.record_event_duration_secs(
            '/jax/core/compile/jaxpr_to_mlir_module_duration', lower_s,
            fun_name=program,
        )
    if from_cache:
        monitoring.record_event('/jax/compilation_cache/cache_hits')
        monitoring.record_event_duration_secs(
            '/jax/compilation_cache/cache_retrieval_time_sec', seconds / 2
        )
    elif written:
        monitoring.record_event('/jax/compilation_cache/cache_misses')
    monitoring.record_event_duration_secs(
        '/jax/core/compile/backend_compile_duration', seconds,
        fun_name=program,
    )


def test_non_compiling_phase_never_claims_persistent_cache_hit():
    """A listening watcher marks a phase from jax's own cache events. A
    phase that does work but no XLA compilation (compiles=False) must not
    read "no program missed the cache" as a 'hit' — a cold
    migrate/allocate would otherwise poison the warm-start evidence."""
    recorder = FlightRecorder()
    watch = CompileWatcher(recorder=recorder).listen()
    try:
        with watch.phase('kv_allocate', 'blocks8', compiles=False):
            pass
        no_compile = _compile_records(recorder)[-1]
        assert no_compile['programs'] == 0
        assert not no_compile['cache_hit']
        # A COMPILING phase whose every program came out of the
        # persistent cache IS the warm fast path ...
        with watch.phase('decode_window', 'b1x1'):
            _jax_compiles('jit(window_fn)', from_cache=True)
            _jax_compiles('jit(merge)', from_cache=True)
        program, _, warm = _compile_records(recorder)[-3:]
        assert warm['cache_hit']
        assert (warm['programs'], warm['cache_hits']) == (2, 2)
        # ... each program a record of its own, inside the phase's.
        assert program['program'] == 'jit(window_fn)'
        assert program['cache_hit'] and program['path'] == 'startup'
        # ``phase`` means on a program's record what it means on the
        # phase's own: the phase kind it belongs to.
        assert (program['phase'], program['shape']) == (
            'decode_window', 'b1x1'
        )
        assert 'program' not in warm and 'path' not in warm
        # ... and one that missed is not.
        with watch.phase('decode_window', 'b1x2'):
            _jax_compiles('jit(window_fn)', from_cache=True)
            _jax_compiles('jit(merge)', from_cache=False)
        cold = _compile_records(recorder)[-1]
        assert not cold['cache_hit']
        assert (cold['programs'], cold['cache_hits']) == (2, 1)
        # The cache-hit event does not leak into the next compile.
        assert not _compile_records(recorder)[-2]['cache_hit']
        # Process-repeat still marks non-compiling phases.
        with watch.phase('kv_allocate', 'blocks8', compiles=False):
            pass
        assert _compile_records(recorder)[-1]['cache_hit']
    finally:
        watch.unlisten()


def test_watcher_that_does_not_listen_knows_only_process_repeat():
    """Without jax's events a phase cannot tell a cache load from a
    compile, so only the process-repeat path may call it a hit (the
    directory count that used to guess is gone)."""
    recorder = FlightRecorder()
    watch = CompileWatcher(recorder=recorder)
    with watch.phase('decode_window', 'b1x1'):
        _jax_compiles('jit(window_fn)', from_cache=True)
    (first,) = _compile_records(recorder)  # and no program record
    assert not first['cache_hit'] and 'programs' not in first
    with watch.phase('decode_window', 'b1x1'):
        pass
    assert _compile_records(recorder)[-1]['cache_hit']


def test_compile_outside_a_phase_is_a_serving_record():
    """... once an engine has been built. Before the first ``engine_init``
    has closed, what an entry point compiles under no phase and no step
    span (its weights) is start-up, and no serving metric may see it."""
    recorder = FlightRecorder()
    watch = CompileWatcher(recorder=recorder).listen()
    try:
        _jax_compiles('jit(fill)', from_cache=False, seconds=0.3)
        with watch.phase('engine_init', 'mistral:b4'):
            pass
        _jax_compiles('jit(prefill_paged_fn)', from_cache=False, seconds=0.2)
    finally:
        watch.unlisten()
    early, _, record = _compile_records(recorder)
    assert early['path'] == 'startup' and early['program'] == 'jit(fill)'
    assert not {'phase', 'during', 'seq'} & set(early)
    assert record['path'] == 'serving' and 'phase' not in record
    assert record['program'] == 'jit(prefill_paged_fn)'
    assert record['duration_s'] == 0.2 and not record['cache_hit']
    assert record['during'] is None and record['seq'] is None
    assert 'relowered' not in record  # no engine call was in flight


def test_phase_scope_namespaces_process_dedup():
    """A second engine in one process builds NEW jit wrappers whose
    warmup really recompiles — the same (kind, shape) under a fresh
    scope must not read as a cache hit."""
    recorder = FlightRecorder()
    watch = CompileWatcher(recorder=recorder)
    scope_a, scope_b = watch.new_scope(), watch.new_scope()
    assert scope_a != scope_b
    with watch.phase('prefill', 'b1x16', scope=scope_a):
        pass
    with watch.phase('prefill', 'b1x16', scope=scope_a):
        pass
    with watch.phase('prefill', 'b1x16', scope=scope_b):
        pass
    hits = [r['cache_hit'] for r in _compile_records(recorder)]
    assert hits == [False, True, False]


def test_second_engine_sharing_the_watcher_starts_cold():
    recorder = FlightRecorder()
    shared = CompileWatcher(recorder=recorder)
    engine_a, _ = _tiny_engine()
    engine_a._compile_watcher = shared
    engine_b, _ = _tiny_engine()
    engine_b._compile_watcher = shared
    assert engine_a._compile_scope != engine_b._compile_scope
    engine_a.warmup()
    first = _compile_records(recorder)
    engine_b.warmup()
    second = _compile_records(recorder)[len(first):]
    assert {(r['phase'], r['shape']) for r in second} == {
        (r['phase'], r['shape']) for r in first
    }
    assert all(not r['cache_hit'] for r in second)


def test_record_backend_init_phase_and_fast_repeat():
    watch = CompileWatcher(recorder=FlightRecorder())
    devices = record_backend_init(watch)
    assert devices[0].platform == 'cpu'
    first = watch.state()['phases'][-1]
    assert first['phase'] == 'backend_init'
    assert first['platform'] == 'cpu'
    assert first['num_devices'] == len(devices)
    record_backend_init(watch)
    assert watch.state()['phases'][-1]['cache_hit']


def test_compile_series_in_exposition():
    """The catalog carries the new series from the first scrape."""
    text = get_registry().render()
    for name in (
        'distllm_compile_seconds',
        'distllm_compile_cache_hits_total',
        'distllm_engine_mfu_measured',
        'distllm_engine_bandwidth_utilization_measured',
        'distllm_engine_roofline_flops_ratio',
        'distllm_engine_roofline_bytes_ratio',
        'distllm_profiler_captures_total',
    ):
        assert f'# TYPE {name} ' in text, name


# ------------------------------------- a program's stages and its clock
def _listening_from_a_clean_slate(recorder) -> CompileWatcher:
    """A listening watcher whose first program inherits nothing: what this
    thread traced and never compiled before (another test's engine) is
    claimed here by a compile that no watcher of the test hears."""
    CompileWatcher().listen().unlisten()  # jax's listeners are installed
    _jax_compiles('jit(earlier)', from_cache=False)
    return CompileWatcher(recorder=recorder).listen()


@pytest.mark.parametrize('events, cache', [
    ({'from_cache': True}, 'hit'),
    ({'from_cache': False, 'written': True}, 'miss'),
    ({'from_cache': False}, 'uncached'),
])
def test_program_record_says_what_the_persistent_cache_did(events, cache):
    recorder = FlightRecorder()
    watch = _listening_from_a_clean_slate(recorder)
    try:
        before = steps.clock()
        _jax_compiles('jit(window_fn)', seconds=0.25, trace_s=(0.5,),
                      lower_s=0.125, **events)
        after = steps.clock()
        _jax_compiles('jit(merge)', from_cache=False)  # claims nothing old
    finally:
        watch.unlisten()
    record, following = _compile_records(recorder)
    assert record['cache'] == cache
    assert record['cache_hit'] is (cache == 'hit')
    assert (record['trace_s'], record['lower_s']) == (0.5, 0.125)
    # t1_s is the clock read at jax's event, t0_s that less its seconds
    assert before <= record['t1_s'] <= after
    assert record['t1_s'] - record['t0_s'] == pytest.approx(0.25, abs=1e-5)
    assert (following['cache'], following['trace_s'], following['lower_s']) == (
        'uncached', 0.0, 0.0
    )
    # the watcher keeps the same two records itself, outside the ring
    kept = watch.state()['programs']
    assert [p['program'] for p in kept] == ['jit(window_fn)', 'jit(merge)']
    stages = ('cache', 'trace_s', 'lower_s', 'duration_s', 't0_s', 't1_s')
    assert {k: kept[0][k] for k in stages} == {k: record[k] for k in stages}


def test_a_real_jit_has_all_three_stages_on_the_step_clock():
    recorder = FlightRecorder()
    watch = _listening_from_a_clean_slate(recorder)
    inner = jax.jit(lambda x: jax.numpy.tanh(x) * 3)

    def outer(x):  # a new function, so this process has not traced it
        return inner(x) @ x + 36

    try:
        before = steps.clock()
        jax.block_until_ready(jax.jit(outer)(np.ones((8, 8), np.float32)))
        after = steps.clock()
    finally:
        watch.unlisten()
    (record,) = [
        r for r in _compile_records(recorder) if r['program'] == 'jit(outer)'
    ]
    assert record['trace_s'] > 0 and record['lower_s'] > 0
    assert before <= record['t0_s'] < record['t1_s'] <= after
    assert record['cache'] in ('hit', 'miss', 'uncached')
    assert record['thread'] == threading.current_thread().name
    # the inner jit was traced inside the outer one's extent, whose seconds
    # hold it: all three stages fit the wall time of the call
    whole = record['trace_s'] + record['lower_s'] + record['duration_s']
    assert whole <= after - before


def test_trace_with_no_compile_does_not_leak_to_another_thread():
    """jax's trace and lowering events are held for the thread they fired
    on: ``jax.eval_shape`` here never reaches a backend compile, and the
    next program of another thread must not inherit its seconds."""
    recorder = FlightRecorder()
    watch = _listening_from_a_clean_slate(recorder)
    try:
        _jax_traces('jit(never_compiled)', 7.0)
        worker = threading.Thread(
            target=_jax_compiles, args=('jit(other)',),
            kwargs={'from_cache': False, 'trace_s': (0.25,)}, name='other',
        )
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
        _jax_compiles('jit(mine)', from_cache=False)
    finally:
        watch.unlisten()
    other, mine = _compile_records(recorder)
    assert (other['thread'], other['trace_s']) == ('other', 0.25)
    assert mine['trace_s'] == 7.0  # this thread's next compile claims it


def test_two_traces_before_one_compile_are_one_record():
    """An outer jit that traces an inner one fires two trace events and
    one backend compile: one record, whose ``trace_s`` holds both."""
    recorder = FlightRecorder()
    watch = _listening_from_a_clean_slate(recorder)
    try:
        _jax_compiles('jit(outer)', from_cache=False, trace_s=(0.5, 0.25),
                      lower_s=0.125)
    finally:
        watch.unlisten()
    (record,) = _compile_records(recorder)
    assert (record['trace_s'], record['lower_s']) == (0.75, 0.125)


def test_a_phase_holds_only_what_its_own_thread_compiles():
    recorder = FlightRecorder()
    watch = _listening_from_a_clean_slate(recorder)
    try:
        with watch.phase('engine_init', 'mistral:b4'):
            worker = threading.Thread(
                target=_jax_compiles, args=('jit(reference)',),
                kwargs={'from_cache': False}, name='ahead',
            )
            worker.start()
            worker.join(timeout=30)
            assert not worker.is_alive()
    finally:
        watch.unlisten()
    program, phase = _compile_records(recorder)
    assert program['thread'] == 'ahead' and 'phase' not in program
    assert phase['programs'] == 0


# ------------------------------------------------ the account (summary)
def test_engine_init_nests_the_engines_phases_and_the_stretches_add_up(
    monkeypatch,
):
    import distllm_tpu.generate.engine.engine as engine_module

    shared = CompileWatcher(recorder=FlightRecorder())
    monkeypatch.setattr(engine_module, 'get_compile_watcher', lambda: shared)
    try:
        engine, _ = _tiny_engine(quantization='int8')
    finally:
        shared.unlisten()
    phases = {p['phase']: p for p in shared.state()['phases']}
    init = phases.pop('engine_init')
    assert init['shape'] == 'mistral:b4'
    # (auto_layout and migrate_params are a TPU's, state_allocate a
    # hybrid model's: the CPU's tiny mistral opens these three)
    assert set(phases) == {'backend_init', 'quantize', 'kv_allocate'}
    for inner in phases.values():
        assert init['t0_s'] <= inner['t0_s'] <= inner['t1_s'] <= init['t1_s']
    until = steps.clock()
    summary = shared.summary(until_s=until)
    assert summary['process_start_s'] == startup.process_start_s()
    assert summary['before_engine_s'] == pytest.approx(
        init['t0_s'] - startup.process_start_s()
    )
    assert summary['engine_init_s'] == pytest.approx(init['duration_s'], abs=1e-5)
    assert (
        summary['before_engine_s'] + summary['engine_init_s']
        + summary['after_engine_s']
    ) == pytest.approx(until - startup.process_start_s(), abs=1e-3)
    assert 0 <= summary['unphased_init_s'] <= summary['engine_init_s']
    assert summary['unphased_init_s'] == pytest.approx(
        init['duration_s'] - sum(p['duration_s'] for p in phases.values())
        - sum(
            p['trace_s'] + p['lower_s'] + p['duration_s']
            for p in shared.state()['programs']
            if p.get('phase') == 'engine_init'
        ),
        abs=1e-4,
    )
    engine.shutdown()


def test_process_start_is_on_the_step_clock_and_before_the_import():
    start = startup.process_start_s()
    assert start == startup.process_start_s()
    assert start <= startup._IMPORTED_S <= steps.clock()
    assert steps.clock() - start < 24 * 3600  # this process, not the boot


def test_programs_survive_the_engine_and_a_wrapped_ring():
    recorder = FlightRecorder(capacity=8)
    watch = _listening_from_a_clean_slate(recorder)
    try:
        engine, _ = _tiny_engine()
        engine._compile_watcher = watch
        engine.warmup()
        engine.shutdown()
    finally:
        watch.unlisten()
    del engine
    assert recorder.total_recorded > recorder.capacity  # the ring wrapped
    programs = watch.state()['programs']
    assert len(programs) > 8
    assert {'jit(window_fn)', 'jit(prefill_fn)'} <= {
        p['program'] for p in programs
    }
    assert all(p['path'] == 'startup' for p in programs)
    for program in programs:
        assert {'trace_s', 'lower_s', 'cache', 't0_s', 't1_s', 'thread',
                'duration_s', 'cache_hit', 't_wall'} <= set(program)
    summary = watch.summary()
    assert summary['programs'] == len(programs)
    assert summary['cache_load_s'] + summary['compile_miss_s'] == pytest.approx(
        sum(p['duration_s'] for p in programs)
    )
    assert json.dumps(watch.state())  # what startup.json writes


def test_summary_leaves_out_what_starts_after_the_cut():
    watch = _listening_from_a_clean_slate(FlightRecorder())
    try:
        _jax_compiles('jit(fill)', from_cache=False, written=True, seconds=2.0)
        with watch.phase('engine_init', 'mistral:b4'):
            with watch.phase('auto_layout', 'b4'):
                _jax_compiles('jit(window_fn)', from_cache=True, seconds=0.5)
            _jax_compiles('jit(loose)', from_cache=False, seconds=0.25,
                          trace_s=(0.25,))
        _jax_compiles('jit(prefill_fn)', from_cache=True, seconds=0.125,
                      trace_s=(1.0,), lower_s=0.5)
        cut = steps.clock()
        time.sleep(0.01)
        _jax_compiles('jit(check)', from_cache=False, written=True,
                      seconds=0.001)
        with watch.phase('engine_init', 'mistral:b4'):  # a second engine
            pass
    finally:
        watch.unlisten()
    whole = watch.summary()
    assert whole['programs'] == 5 and whole['cache_miss_programs'] == 1
    summary = watch.summary(until_s=cut)
    assert summary['until_s'] == cut and summary['programs'] == 4
    assert summary['cache_load_s'] == pytest.approx(0.625)
    assert summary['compile_miss_s'] == pytest.approx(2.25)
    assert summary['trace_lower_s'] == pytest.approx(1.75)
    # a miss under a second is the cache's floor at work, not an eviction
    assert summary['cache_miss_programs'] == 1
    assert summary['after_engine_program_s'] == pytest.approx(1.625)
    first = next(
        p for p in watch.state()['phases'] if p['phase'] == 'engine_init'
    )
    assert summary['engine_init_s'] == pytest.approx(first['duration_s'], abs=1e-5)
    assert summary['after_engine_s'] == pytest.approx(cut - first['t1_s'])
    # under no inner phase and no program: the phase less auto_layout and
    # the loose program's half second
    inner = next(
        p for p in watch.state()['phases'] if p['phase'] == 'auto_layout'
    )
    assert summary['unphased_init_s'] == pytest.approx(
        max(0.0, first['duration_s'] - inner['duration_s'] - 0.5), abs=1e-5
    )
    # a watcher that saw no engine: one stretch, and no account of __init__
    bare = CompileWatcher(recorder=FlightRecorder()).summary(until_s=cut)
    assert bare['before_engine_s'] == cut - startup.process_start_s()
    assert bare['engine_init_s'] is None and bare['unphased_init_s'] is None


# --------------------------------------------------- debug bundle satellite
def test_debug_bundle_includes_startup_state(tmp_path):
    paths = dump_debug_bundle(tmp_path / 'bundle', reason='startup test')
    assert 'startup' in paths
    state = json.loads((tmp_path / 'bundle' / 'startup.json').read_text())
    assert set(state) == {'compile', 'profiler'}
    assert {'active', 'phases', 'programs'} <= set(state['compile'])
    assert 'captures_total' in state['profiler']


def test_debug_bundle_names_dead_phase_mid_stall(tmp_path):
    """Bundle dumped while a phase is in flight (the init-stall scenario)
    attributes the dead phase."""
    from distllm_tpu.observability.startup import get_compile_watcher

    watch = get_compile_watcher()
    with watch.phase('migrate_params', 'params'):
        dump_debug_bundle(tmp_path / 'stall', reason='wedged migrate')
    state = json.loads((tmp_path / 'stall' / 'startup.json').read_text())
    assert state['compile']['active'][-1]['phase'] == 'migrate_params'


# ------------------------------------------- measured XLA cost (xla_cost)
def test_warmup_prices_executables_from_cost_analysis():
    engine, _ = _tiny_engine()
    assert engine.measured_costs() == {}  # warmup fills it
    engine.warmup()
    costs = engine.measured_costs()
    assert set(costs) == {'prefill', 'decode'}
    for cost in costs.values():
        assert cost['flops'] > 0
        assert cost['bytes_accessed'] > 0
        assert cost['source'] in ('aot', 'lowered')


def test_measured_gauges_and_ratios_published_per_step():
    engine, _ = _tiny_engine()
    engine.warmup()
    before = engine.flight.total_recorded
    engine.generate_ids(
        [[5, 9, 12]], SamplingParams(temperature=0.0, max_tokens=4)
    )
    new = engine.flight.snapshot()[
        -(engine.flight.total_recorded - before):
    ]
    decode = [r for r in new if r['kind'] == 'decode']
    assert decode, new
    # Flight records carry the measured twin beside the analytic fields.
    for record in decode:
        assert record['mfu_measured'] > 0
        assert record['bw_util_measured'] > 0
        assert record['mfu'] > 0
    # Prefill dispatches at varying (batch, bucket) shapes: the priced
    # largest-shape executable must NOT be published over their wall
    # time (it would inflate by the shape ratio) — cost is visible via
    # measured_costs() only.
    prefill = [r for r in new if r['kind'] == 'prefill']
    assert prefill and all('mfu_measured' not in r for r in prefill)
    # Gauges: measured MFU next to the analytic one, ratios recorded.
    assert instruments.ENGINE_MFU_MEASURED.labels(kind='decode').value > 0
    assert (
        instruments.ENGINE_BW_UTIL_MEASURED.labels(kind='decode').value > 0
    )
    flops_ratio = instruments.ENGINE_ROOFLINE_FLOPS_RATIO.labels(
        kind='decode'
    ).value
    bytes_ratio = instruments.ENGINE_ROOFLINE_BYTES_RATIO.labels(
        kind='decode'
    ).value
    assert flops_ratio > 0 and bytes_ratio > 0


def test_attribution_off_skips_measured_gauges_but_tokens_identical():
    on_engine, _ = _tiny_engine()
    on_engine.warmup()
    off_engine, _ = _tiny_engine(attribution=False)
    off_engine.warmup()
    prompts = [[7, 3, 22, 31]]
    sp = SamplingParams(temperature=0.0, max_tokens=5)
    on_tokens = on_engine.generate_ids(prompts, sp)
    before = off_engine.flight.total_recorded
    assert on_tokens == off_engine.generate_ids(prompts, sp)
    new = off_engine.flight.snapshot()[
        -(off_engine.flight.total_recorded - before):
    ]
    decode = [r for r in new if r['kind'] == 'decode']
    assert decode and all('mfu_measured' not in r for r in decode)


def test_price_callable_handles_aot_and_failures():
    from distllm_tpu.observability.xla_cost import price_callable

    jitted = jax.jit(lambda a, b: a @ b)
    a = np.zeros((16, 16), np.float32)
    cost = price_callable(jitted, a, a)
    assert cost is not None and cost.flops > 0
    assert cost.source == 'lowered'
    aot = jitted.lower(a, a).compile()
    cost_aot = price_callable(aot)
    assert cost_aot is not None and cost_aot.flops == cost.flops
    assert cost_aot.source == 'aot'
    # Pricing is telemetry: wrong args degrade to None, never raise.
    assert price_callable(jitted, np.zeros((3, 5)), np.zeros((7, 2))) is None


# ------------------------------------------------- bounded profiler capture
def test_profiler_capture_bounded_and_rejecting(tmp_path):
    capture = ProfilerCapture()
    assert capture.state()['active'] is None
    assert capture.start(tmp_path / 'trace', max_seconds=30.0)
    assert capture.state()['active']['log_dir'].endswith('trace')
    # Second start is rejected, not queued — jax's profiler is global.
    assert not capture.start(tmp_path / 'other')
    assert 'already active' in capture.state()['last_error']
    assert capture.stop()
    assert capture.state()['active'] is None
    assert capture.state()['captures_total'] == 1
    assert not capture.stop()  # idempotent


def test_profiler_capture_auto_stops_at_bound(tmp_path):
    capture = ProfilerCapture()
    assert capture.start(tmp_path / 'bounded', max_seconds=0.2)
    deadline = time.monotonic() + 10.0
    # captures_total increments only after the auto-stop flush completes.
    while (
        not capture.state()['captures_total']
        and time.monotonic() < deadline
    ):
        time.sleep(0.05)
    state = capture.state()
    assert state['captures_total'] == 1, state
    assert state['active'] is None


def test_profiler_capture_swallows_backend_errors(tmp_path, monkeypatch):
    """The bench satellite: an unsupported-backend profiler error must
    not kill the caller."""
    capture = ProfilerCapture()

    def boom(*args, **kwargs):
        raise RuntimeError('profiler unsupported on this backend')

    monkeypatch.setattr(jax.profiler, 'start_trace', boom)
    assert not capture.start(tmp_path / 'nope')
    assert 'unsupported' in capture.state()['last_error']
    assert capture.state()['active'] is None
    result = capture.capture(tmp_path / 'nope2', seconds=0.1)
    assert not result['ok'] and not result['rejected']
    assert 'unsupported' in result['error']
