"""Resilience layer tests (ISSUE 15): deterministic fault injection,
engine crash-domain recovery (retry → quarantine, deadlines), and
SLO-aware admission shedding (docs/resilience.md).

The chaos matrix is the acceptance contract: under each injected fault
class the engine either RECOVERS (retry succeeds, tokens bit-identical
to the fault-free run in greedy fp32) or fails ONLY the affected
requests with a recorded error — never wedges the window loop, never
drops a request silently.
"""

from __future__ import annotations

import pytest

import jax

from distllm_tpu.generate.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.generate.engine.engine import RequestState
from distllm_tpu.models import mistral
from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.resilience import (
    FAULT_SITES,
    EngineLoadView,
    EngineOverloaded,
    FaultInjector,
    InjectedFault,
    get_fault_injector,
    parse_fault_spec,
    predict_ttft,
    shed_decision,
)


@pytest.fixture(autouse=True)
def _disarm_injector():
    """Every test starts and ends with an inert process injector."""
    injector = get_fault_injector()
    injector.disarm()
    yield injector
    injector.disarm()


@pytest.fixture
def _fake_clock(monkeypatch):
    """Deterministic clock for the deadline tests: the engine's
    ``time.monotonic`` and the injector's ``time.sleep`` share one fake
    clock that only an injected ``slow_window`` stall advances, so a
    deadline fires exactly when a stall was injected and never because
    the machine (six test workers on shared cores) was slow."""
    import types

    from distllm_tpu.generate.engine import engine as engine_mod
    from distllm_tpu.resilience import faults as faults_mod

    clock = types.SimpleNamespace(now=1000.0)

    def sleep(seconds):
        clock.now += seconds

    shim = types.SimpleNamespace(monotonic=lambda: clock.now, sleep=sleep)
    monkeypatch.setattr(engine_mod, 'time', shim)
    monkeypatch.setattr(faults_mod, 'time', shim)
    return clock


# ------------------------------------------------------------ faults unit
class TestFaultInjector:
    def test_inert_by_default(self):
        injector = FaultInjector()
        assert not injector.armed
        assert injector.fire('dispatch') is None
        injector.fail('dispatch')  # no raise
        assert injector.maybe_sleep('slow_window') == 0.0

    def test_deterministic_schedule(self):
        injector = FaultInjector()
        injector.arm('dispatch', times=2, after=3)
        fires = [injector.fire('dispatch') is not None for _ in range(8)]
        # 3 skipped calls, 2 fires, then exhausted.
        assert fires == [False, False, False, True, True,
                         False, False, False]
        assert injector.fired('dispatch') == 2

    def test_seeded_probability_reproducible(self):
        a, b = FaultInjector(), FaultInjector()
        for injector in (a, b):
            injector.arm('tier_io', times=None, prob=0.5, seed=7)
        seq_a = [a.fire('tier_io') is not None for _ in range(32)]
        seq_b = [b.fire('tier_io') is not None for _ in range(32)]
        assert seq_a == seq_b
        assert any(seq_a) and not all(seq_a)

    def test_unknown_site_rejected(self):
        injector = FaultInjector()
        with pytest.raises(ValueError):
            injector.arm('no-such-site')
        injector.arm('dispatch')
        with pytest.raises(ValueError):
            injector.fire('no-such-site')

    def test_fail_raises_injected_fault(self):
        injector = FaultInjector()
        injector.arm('dispatch', times=1)
        with pytest.raises(InjectedFault) as err:
            injector.fail('dispatch')
        assert err.value.site == 'dispatch'
        injector.fail('dispatch')  # exhausted: no raise

    def test_fail_io_raises_oserror(self):
        injector = FaultInjector()
        injector.arm('tier_io', times=1)
        with pytest.raises(OSError):
            injector.fail_io('tier_io')

    def test_env_spec_parse(self):
        specs = parse_fault_spec(
            'dispatch:times=2:after=4, slow_window:delay_s=0.2,'
            'tier_io:prob=0.5:seed=7:times=inf'
        )
        assert specs[0] == {'site': 'dispatch', 'times': 2, 'after': 4}
        assert specs[1] == {'site': 'slow_window', 'delay_s': 0.2}
        assert specs[2]['times'] is None
        with pytest.raises(ValueError):
            parse_fault_spec('typo_site:times=1')
        with pytest.raises(ValueError):
            parse_fault_spec('dispatch:bogus_key=1')

    def test_fire_counts_metric_and_flight(self):
        injector = FaultInjector()
        injector.arm('dispatch', times=1)
        before = _metrics.RESILIENCE_FAULTS.labels(site='dispatch').value
        from distllm_tpu.observability.flight import get_flight_recorder

        total_before = get_flight_recorder().total_recorded
        assert injector.fire('dispatch') is not None
        assert (
            _metrics.RESILIENCE_FAULTS.labels(site='dispatch').value
            == before + 1
        )
        records = get_flight_recorder().snapshot()
        assert get_flight_recorder().total_recorded == total_before + 1
        assert records[-1]['kind'] == 'fault'
        assert records[-1]['site'] == 'dispatch'

    def test_sites_catalogued(self):
        # The metric pre-registration list and the site catalog must
        # agree (the FLIGHT_KINDS pattern).
        assert set(_metrics.FAULT_SITE_LABELS) == set(FAULT_SITES)


# ------------------------------------------------------- admission unit
class TestAdmissionPolicy:
    def _view(self, **kw):
        base = dict(
            waiting_tokens=0, pending_decode_tokens=0, num_waiting=0,
            num_running=0, max_num_seqs=4, decode_steps=4,
            prefill_s_per_token=0.01, window_s=0.1, slo_s=1.0,
        )
        base.update(kw)
        return EngineLoadView(**base)

    def test_monotonic_in_backlog(self):
        idle = predict_ttft(self._view(), prompt_tokens=10)
        queued = predict_ttft(
            self._view(waiting_tokens=500, num_waiting=5), prompt_tokens=10
        )
        saturated = predict_ttft(
            self._view(
                waiting_tokens=500, num_waiting=5, num_running=4,
                pending_decode_tokens=400,
            ),
            prompt_tokens=10,
        )
        assert idle < queued < saturated
        # The decode-drain term: one window serves max_num_seqs x
        # decode_steps tokens, so 400 pending tokens = 25 windows.
        drain_only = predict_ttft(
            self._view(pending_decode_tokens=400, prefill_s_per_token=0.0),
            prompt_tokens=0,
        )
        assert drain_only == pytest.approx(25 * 0.1)

    def test_shed_decision_thresholds(self):
        admit, predicted, retry = shed_decision(self._view(), 10)
        assert admit and retry == 0.0 and predicted > 0
        admit, predicted, retry = shed_decision(
            self._view(waiting_tokens=100_000), 10
        )
        assert not admit
        assert 1.0 <= retry <= 60.0
        # No SLO = no shedding, whatever the backlog.
        admit, _, _ = shed_decision(
            self._view(waiting_tokens=100_000, slo_s=0.0), 10
        )
        assert admit


# ------------------------------------------------------------ chaos matrix
def _tiny_engine(**cfg_kwargs):
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

    engine_kw = dict(
        block_size=4,
        num_blocks=32,
        max_num_seqs=2,
        max_model_len=64,
        prefer_native_allocator=False,
    )
    engine_kw.update(cfg_kwargs)
    engine = LLMEngine(
        cfg, params, IdTokenizer(), EngineConfig(**engine_kw)
    )
    return cfg, params, engine


RECOVER = dict(max_dispatch_retries=3, retry_backoff_s=0.0)
GREEDY = SamplingParams(temperature=0.0, max_tokens=6)
PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7]]


def _clean_tokens():
    _, _, engine = _tiny_engine()
    return engine.generate_ids(PROMPTS, GREEDY)


class TestChaosMatrix:
    def test_dispatch_fault_recovers_bit_identical(self, _disarm_injector):
        clean = _clean_tokens()
        _disarm_injector.arm('dispatch', times=2)
        _, _, engine = _tiny_engine(**RECOVER)
        got = engine.generate_ids(PROMPTS, GREEDY)
        assert got == clean
        assert engine._stats['window_retries'] >= 2
        assert engine._stats['recoveries'] >= 1
        assert not engine._stats.get('quarantined_requests')

    def test_sched_exhausted_fault_recovers(self, _disarm_injector):
        clean = _clean_tokens()
        _disarm_injector.arm('sched_exhausted', times=2)
        _, _, engine = _tiny_engine(**RECOVER)
        got = engine.generate_ids(PROMPTS, GREEDY)
        assert got == clean
        assert engine._stats['window_retries'] >= 1

    def test_persistent_fault_quarantines_only_affected(
        self, _disarm_injector
    ):
        """A fault that outlives the retry budget fails the requests in
        the failing dispatches — with errors recorded — then later
        requests serve normally once the fault clears. Never a wedge."""
        clean = _clean_tokens()
        # Exactly enough fires to exhaust the first batch's retry budget
        # (both requests share the padded prefill dispatch, so each fire
        # charges both; the third consecutive failure quarantines), then
        # the injector runs dry and the engine heals.
        _disarm_injector.arm('dispatch', times=3)
        _, _, engine = _tiny_engine(max_dispatch_retries=2,
                                    retry_backoff_s=0.0)
        failed = engine.generate_ids(PROMPTS, GREEDY)
        assert failed == [[], []]  # affected requests failed, recorded
        assert engine._stats['quarantined_requests'] == 2
        # The loop is alive: fresh requests serve bit-identically.
        healed = engine.generate_ids(PROMPTS, GREEDY)
        assert healed == clean

    def test_quarantine_records_error_and_frees_blocks(
        self, _disarm_injector
    ):
        _disarm_injector.arm('dispatch', times=None)  # permanent
        _, _, engine = _tiny_engine(max_dispatch_retries=1,
                                    retry_backoff_s=0.0)
        rid = engine.add_request(list(PROMPTS[0]), GREEDY)
        while engine.has_unfinished:
            engine.step()
        _disarm_injector.disarm()
        request = engine._finished.pop(rid)
        assert request.state is RequestState.FAILED
        assert request.finish_reason == 'dispatch_failed'
        assert request.error
        # Every block is back: nothing leaked through quarantine.
        assert engine.sched.num_free_blocks == engine.config.num_blocks - 1
        assert engine.sched.num_running == 0

    def test_device_put_fault_degrades_to_cold_prefill(
        self, _disarm_injector, tmp_path
    ):
        """A failed promotion transfer must fall back to cold prefill —
        same tokens, tier error counted, no exception in admission."""
        pool = dict(num_blocks=12, max_num_seqs=2, max_model_len=48,
                    enable_prefix_cache=True)
        prompt_a = list(range(1, 25))
        prompt_b = list(range(30, 54))
        cfg, params, engine = _tiny_engine(
            host_kv_tier_bytes=64 << 20, **pool
        )
        _, _, ref = _tiny_engine(**pool)
        errors_before = _metrics.PREFIX_TIER_ERRORS.labels(
            tier='host'
        ).value
        _disarm_injector.arm('device_put', times=None)
        for prompt in (prompt_a, prompt_b, prompt_a):
            got = engine.generate_ids([prompt], GREEDY)[0]
            want = ref.generate_ids([prompt], GREEDY)[0]
            assert got == want
        _disarm_injector.disarm()
        # The second PROMPT_A arrival found tier entries, began a
        # promotion, hit the injected transfer fault, and re-prefilled.
        assert engine._stats.get('tier_promotion_failures', 0) >= 1
        assert (
            _metrics.PREFIX_TIER_ERRORS.labels(tier='host').value
            > errors_before
        )

    def test_tier_io_fault_degrades_to_miss(
        self, _disarm_injector, tmp_path
    ):
        """Injected disk-tier IO errors: spills and loads degrade to
        misses (counted), generation stays bit-exact, nothing raises
        into add_request."""
        pool = dict(num_blocks=12, max_num_seqs=2, max_model_len=48,
                    enable_prefix_cache=True)
        prompt_a = list(range(1, 25))
        prompt_b = list(range(30, 54))
        cfg, params, engine = _tiny_engine(
            host_kv_tier_bytes=2048,  # a couple of blocks: disk matters
            disk_kv_tier_dir=str(tmp_path / 'tier'),
            **pool,
        )
        _, _, ref = _tiny_engine(**pool)
        errors_before = _metrics.PREFIX_TIER_ERRORS.labels(
            tier='disk'
        ).value
        _disarm_injector.arm('tier_io', times=None)
        for prompt in (prompt_a, prompt_b, prompt_a, prompt_b):
            got = engine.generate_ids([prompt], GREEDY)[0]
            want = ref.generate_ids([prompt], GREEDY)[0]
            assert got == want
        _disarm_injector.disarm()
        assert (
            _metrics.PREFIX_TIER_ERRORS.labels(tier='disk').value
            > errors_before
        )

    def test_slow_window_deadline_times_out_and_frees(
        self, _disarm_injector, _fake_clock
    ):
        """A stalled window loop: the per-request deadline fires, the
        request finishes with a timeout status, and its blocks free."""
        _disarm_injector.arm('slow_window', times=None, delay_s=0.06)
        _, _, engine = _tiny_engine(
            request_deadline_s=0.05, decode_steps=2, **RECOVER
        )
        outs = engine.generate_ids(
            [PROMPTS[0]], SamplingParams(temperature=0.0, max_tokens=40)
        )
        _disarm_injector.disarm()
        assert len(outs[0]) < 40  # timed out mid-generation
        assert engine._stats['quarantined_requests'] == 1
        assert engine.sched.num_free_blocks == engine.config.num_blocks - 1
        # A later request is unaffected (deadline is per-request).
        fresh = engine.generate_ids([PROMPTS[1]], GREEDY)[0]
        _, _, ref = _tiny_engine()
        assert fresh == ref.generate_ids([PROMPTS[1]], GREEDY)[0]

    def test_deadline_timeout_status_on_request(
        self, _disarm_injector, _fake_clock
    ):
        _disarm_injector.arm('slow_window', times=None, delay_s=0.06)
        _, _, engine = _tiny_engine(
            request_deadline_s=0.05, decode_steps=2, **RECOVER
        )
        rid = engine.add_request(
            list(PROMPTS[0]),
            SamplingParams(temperature=0.0, max_tokens=40),
        )
        while engine.has_unfinished:
            engine.step()
        _disarm_injector.disarm()
        request = engine._finished.pop(rid)
        assert request.state is RequestState.FAILED
        assert request.finish_reason == 'timeout'
        assert 'request_deadline_s' in (request.error or '')

    def test_prefill_fault_never_decodes_unwritten_kv(
        self, _disarm_injector
    ):
        """A failed prefill dispatch re-prefills on retry — the decode
        gate must hold, so recovered tokens match the clean run exactly
        (decoding over unwritten KV would corrupt them silently)."""
        clean = _clean_tokens()
        # after=0: the FIRST dispatch (admission prefill) faults.
        _disarm_injector.arm('dispatch', times=1, after=0)
        _, _, engine = _tiny_engine(**RECOVER)
        got = engine.generate_ids(PROMPTS, GREEDY)
        assert got == clean

    def test_recovery_off_preserves_legacy_raise(self, _disarm_injector):
        _disarm_injector.arm('dispatch', times=1)
        _, _, engine = _tiny_engine()  # max_dispatch_retries=0
        with pytest.raises(InjectedFault):
            engine.generate_ids(PROMPTS, GREEDY)


# ------------------------------------------------------------- overload
class TestOverloadShedding:
    def _run(self, engine, workload):
        from distllm_tpu.generate.loadgen import run_loadgen

        # Warm the serving shapes the workload uses (bucket-16 and
        # bucket-32 prefills + the decode window): compiles inside the
        # measured run would poison every TTFT, and the warm generates
        # also feed the shed arm's EWMA predictor measured rates.
        engine.generate_ids(
            [list(range(1, 9)), list(range(1, 33))],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        # The warm generates' durations INCLUDED the jit compiles, so
        # they poison the EWMA with rates off by orders of magnitude
        # (production engines warm via engine.warmup(), which bypasses
        # _record_step entirely); drop them so the predictor sees only
        # steady-state measurements.
        engine._ewma.clear()
        return run_loadgen(engine, workload)

    def _workload(self):
        from distllm_tpu.generate.loadgen import Arrival

        # Four paced arrivals the engine serves comfortably inside the
        # SLO, then a burst far beyond roofline-predicted capacity at
        # t=2.0 — on this 2-slot engine the burst's queue drain takes
        # many times the SLO, so a no-shedding baseline must miss for
        # most of it.
        paced = [
            Arrival(at_s=0.4 * i, prompt_ids=tuple(range(1, 9)),
                    max_tokens=4, session=None)
            for i in range(4)
        ]
        burst = [
            Arrival(at_s=2.0, prompt_ids=tuple(range(10 + i, 42 + i)),
                    max_tokens=12, session=None)
            for i in range(48)
        ]
        return paced + burst

    def test_shed_beats_no_shed_on_slo_attainment(self):
        workload = self._workload()
        slo = dict(ttft_slo_s=0.25, decode_steps=2)

        _, _, baseline = _tiny_engine(**slo)
        base = self._run(baseline, workload)
        assert base.shed_requests == 0
        base_total = base.slo_met + base.slo_missed
        base_attain = base.slo_met / base_total

        _, _, shedding = _tiny_engine(admission_control=True, **slo)
        shed = self._run(shedding, workload)
        assert shed.shed_requests > 0
        assert shed.shed_rate and 0 < shed.shed_rate < 1
        admitted_total = shed.slo_met + shed.slo_missed
        assert admitted_total == len(workload) - shed.shed_requests
        attain = shed.slo_met / admitted_total
        # The acceptance bar: admitted requests' SLO attainment stays
        # ABOVE the no-shedding baseline under the same offered load.
        assert attain > base_attain
        # Alignment contract: shed arrivals hold empty/None slots.
        assert len(shed.tokens_by_request) == len(workload)
        assert len(shed.ttft_by_request) == len(workload)

    def test_shed_records_carry_retry_after(self):
        workload = self._workload()
        # Tighter SLO than the attainment test: this test only cares
        # that every shed carries an honest Retry-After, so it forces a
        # decisive shed regime.
        _, _, engine = _tiny_engine(
            admission_control=True, ttft_slo_s=0.1, decode_steps=2
        )
        before = engine.flight.total_recorded
        report = self._run(engine, workload)
        assert report.shed_requests > 0
        records = engine.flight.snapshot()
        grew = engine.flight.total_recorded - before
        sheds = [
            r for r in records[-grew:] if r.get('kind') == 'shed'
        ]
        assert len(sheds) == report.shed_requests
        assert all(r['retry_after_s'] >= 1.0 for r in sheds)
        assert all(r['reason'] == 'overload' for r in sheds)

    def test_engine_overloaded_carries_honest_retry_after(self):
        _, _, engine = _tiny_engine(
            admission_control=True, ttft_slo_s=1e-9
        )
        with pytest.raises(EngineOverloaded) as err:
            engine.add_request(list(range(1, 30)))
        assert err.value.retry_after_s >= 1.0
        assert err.value.predicted_ttft_s > 0
        # Nothing was enqueued for the shed arrival.
        assert engine.sched.num_waiting == 0
        assert not engine._requests

    def test_admission_control_requires_slo(self):
        with pytest.raises(Exception):
            EngineConfig(admission_control=True)


# ------------------------------------------------------- chaos via loadgen
def test_loadgen_chaos_smoke(_disarm_injector):
    """The gen_chaos stage's core loop at unit scale: faults firing mid
    open-loop run, nonzero goodput, recovery, fault-off token identity."""
    from distllm_tpu.generate.loadgen import (
        LoadgenConfig,
        build_workload,
        run_loadgen,
    )

    load_cfg = LoadgenConfig(
        seed=0, num_requests=10, rate_rps=40.0, num_sessions=2,
        warm_fraction=0.5, prefix_tokens=8, prompt_tokens=(4, 10),
        output_tokens=(3, 6), vocab_size=64,
    )
    workload = build_workload(load_cfg)
    engine_kw = dict(
        enable_prefix_cache=True, ttft_slo_s=5.0, decode_steps=2, **RECOVER
    )
    _, _, engine = _tiny_engine(**engine_kw)
    clean = run_loadgen(engine, workload)

    _disarm_injector.arm('dispatch', times=2, after=2)
    _disarm_injector.arm('slow_window', times=1, delay_s=0.01)
    _, _, chaos_engine = _tiny_engine(**engine_kw)
    chaos = run_loadgen(chaos_engine, workload)
    _disarm_injector.disarm()

    assert chaos.tokens_by_request == clean.tokens_by_request
    assert chaos.goodput_tokens > 0
    assert chaos.window_retries >= 1
    assert chaos.recoveries >= 1
    assert chaos.quarantined == 0 and chaos.failed_requests == 0


def test_gen_chaos_stage_cpu_smoke(_disarm_injector):
    """The chaos scenario end to end, three arms on ONE warmed engine
    (``serving_smoke.build_engine``): the open-loop schedule served clean;
    the same schedule with a dispatch raise, a window stall and an injected
    scheduler exhaustion armed on fixed call counts while load keeps
    arriving; and a denser schedule under admission control with an SLO no
    request can meet. Every armed site fired, at least one recovery, no
    quarantine and no failed request (the schedule is survivable by
    construction), goodput while faults fired, chaos tokens bit-identical
    to the clean arm (greedy float32: recovery replays, it does not
    approximate), and the overload arm sheds."""
    from distllm_tpu.generate.loadgen import build_workload, run_loadgen
    from serving_smoke import build_engine, workload_config

    fault_schedule = (
        ('dispatch', dict(times=2, after=4)),
        ('slow_window', dict(times=2, delay_s=0.02, after=2)),
        ('sched_exhausted', dict(times=1, after=10)),
    )
    engine = build_engine(
        ttft_slo_s=30.0, request_deadline_s=60.0, max_dispatch_retries=3,
        retry_backoff_s=0.01,
    )
    try:
        workload = build_workload(workload_config())
        clean = run_loadgen(engine, workload)
        # The chaos arm starts where the clean arm did: on an empty cache.
        engine._evict_cached_blocks(engine.config.num_blocks)
        for site, kwargs in fault_schedule:
            _disarm_injector.arm(site, **kwargs)
        chaos = run_loadgen(engine, workload)
        fired = {
            site: _disarm_injector.fired(site) for site, _ in fault_schedule
        }
        _disarm_injector.disarm()

        engine.config.ttft_slo_s = 1e-9  # no prediction can meet it
        engine.admission_control = True
        overload = run_loadgen(
            engine,
            build_workload(
                workload_config(seed=1, num_requests=32, rate_rps=400.0)
            ),
        )
    finally:
        engine.shutdown()
    assert all(count >= 1 for count in fired.values()), fired
    assert chaos.tokens_by_request == clean.tokens_by_request
    assert chaos.recoveries >= 1 and chaos.window_retries >= 1
    assert chaos.quarantined == 0 and chaos.failed_requests == 0
    assert chaos.goodput_tokens > 0
    assert overload.shed_requests > 0 and 0 < overload.shed_rate <= 1
    assert len(overload.tokens_by_request) == 32  # a shed keeps its slot
