"""``LLMEngine`` over ``models/solar_open2.py`` at toy widths on the CPU (the
model itself: ``test_solar_open2.py``): greedy generation against the plain
reference's LOGITS (every generated token within a thousandth of a standard
deviation of the reference's largest, teacher-forced), what a finished request
leaves in its slot and pages, chunked prefill, slots turned over, a slot
reused after a longer holder, preemption and re-admission, the refusals of a
model with state, warm-up, the window's and the dispatch's records, and the
cell's own check on the wrong programs its limits have to catch."""

import jax
import numpy as np
import pytest

from benchmarks import reference_solar_open2 as ref
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from solar_open2_toy import BLOCK, NoTokenizer, make_engine, prompt, tiny


def assert_teacher_forced(hf, params, prompts, outputs, limit=1e-3):
    """Every generated token is the reference's greedy token given the same
    history, or within ``limit`` standard deviations of it (float32 on both
    sides: a tie is the one way to differ)."""
    for p, o in zip(prompts, outputs):
        tokens = list(p) + list(o)[:-1]
        at = len(p) - 1 + np.arange(len(o))[None]
        logits = ref.solar_open2_logits(params, hf, np.asarray(tokens)[None], at)
        assert ref.token_gaps(logits, [o]).max() < limit


def _since(engine, before):
    return engine.flight.snapshot()[before - engine.flight.total_recorded:]


# fewer tokens than taps, one span, a span's end, and several spans
@pytest.mark.parametrize('n', [1, 2, 3, 8, 20])
def test_generate_ids_is_the_references_greedy(n):
    hf, params, engine = make_engine()
    p = prompt(np.random.default_rng(n), n)
    before = engine.flight.total_recorded
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=7))
    assert len(out[0]) == 7
    assert_teacher_forced(hf, params, [p], out)
    # 3 KDA layers hold a matrix state and the convolutions' rows, the 2
    # attention layers pages; the engine read all of it from cache_spec().
    pool = engine.telemetry['state_pool']
    assert pool['slots'] == 4 and pool['bytes_per_slot'] == 3 * (3 * 72 + 3 * 8 * 8) * 4
    assert sorted((leaf['count'], leaf['shape']) for leaf in pool['leaves']) == [
        (3, [3, 8, 8]), (3, [3, 72]),
    ]
    assert engine.telemetry['kv_pools']['kv']['block_shape'] == [BLOCK, 8]
    assert engine.telemetry['kv_pools']['kv']['layers'] == 2
    records = _since(engine, before)
    (request,) = [r for r in records if r['kind'] == 'request']
    assert {'state_slot', 'kv_first_block', 'kv_tail_block'} <= set(request)
    windows = [r for r in records if r['kind'] == 'decode']
    fields = {'kv_blocks', 'state_rows', 'moe_pairs', 'moe_pairs_held'}
    assert windows and all(fields <= set(r) for r in windows)
    # one live row: a step of it reads and writes its slot once, and routes
    # 2 experts in each of 5 layers, all 8 held
    steps = sum(r['tokens'] for r in windows)
    assert steps == 6 == sum(r['state_rows'] for r in windows)
    assert sum(r['moe_pairs'] for r in windows) == steps * 2 * 5
    assert sum(r['moe_pairs_held'] for r in windows) == steps * 2 * 5
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert prefills and all(r['route'] in ('paged', 'chunk') for r in prefills)
    assert sum(r['tokens'] for r in prefills) == n


def test_the_state_and_pages_a_finished_request_left_are_the_references():
    """What the benchmark's content limits read: the ``request`` record
    names the slot and the first and last block a request held; the pools
    keep what they held."""
    hf, params, engine = make_engine()
    rng = np.random.default_rng(3)
    prompts = [prompt(rng, 6), prompt(rng, 19), prompt(rng, 11)]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=13)
    )
    records = sorted(
        (r for r in _since(engine, before) if r['kind'] == 'request'),
        key=lambda r: r['request_id'],
    )
    assert sorted(r['state_slot'] for r in records) == [0, 1, 2]
    for p, o, r in zip(prompts, outputs, records):
        fed = list(p) + list(o)[:-1]
        _, held = ref.forward(params, hf, np.asarray(fed)[None], [[0]])
        state = engine.state_pool.state
        for xi, (want_state, want_conv) in enumerate(held[0]['kda']):  # all three
            assert ref.content_error(state['kda'][xi][r['state_slot']], want_state) < 1e-5
            assert ref.content_error(state['conv'][xi][r['state_slot']], want_conv) < 1e-5
        want_k, want_v = held[0]['gqa'][0]
        for pool, want in ((engine.kv.k, want_k), (engine.kv.v, want_v)):
            first = np.asarray(pool[0][np.asarray([r['kv_first_block']])])[0]
            assert ref.content_error(first, want[:BLOCK]) < 1e-5
            tail = np.asarray(pool[0][np.asarray([r['kv_tail_block']])])[0]
            at = (len(fed) - 1) // BLOCK * BLOCK
            assert ref.content_error(tail[:len(fed) - at], want[at:]) < 1e-5


def test_more_prompts_than_slots_turn_every_slot_over():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(1)
    prompts = [prompt(rng, n) for n in (5, 19, 11, 30, 7, 3, 14, 9, 2)]
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=10)
    )
    assert_teacher_forced(hf, params, prompts, outputs)


def test_a_slot_reused_after_a_longer_holder_starts_from_zero():
    hf, params, engine = make_engine(max_num_seqs=1)
    rng = np.random.default_rng(2)
    sampling = SamplingParams(temperature=0.0, max_tokens=6)
    engine.generate_ids([prompt(rng, 17)], sampling)
    # The one slot now holds the first request's state; the next request
    # takes it, alone and after a call that left the pipeline empty.
    for n in (1, 2, 4, 13):  # fewer tokens than taps, one span, and chunks
        later = prompt(rng, n)
        out = engine.generate_ids([later], sampling)
        assert_teacher_forced(hf, params, [later], out)


def test_a_preempted_request_is_admitted_again_from_zero_state():
    # 10 usable blocks of 4 tokens; two rows of 12 + 20 tokens need 16.
    from distllm_tpu.observability import instruments

    hf, params, engine = make_engine(num_blocks=11, max_num_seqs=2)
    # As if finished requests had used none of their budgets: the
    # look-ahead then admits both rows, and the pool runs short under them.
    engine._ewma['budget_use'] = 0.0
    before = instruments.SCHED_PREEMPTIONS.value
    rng = np.random.default_rng(3)
    prompts = [prompt(rng, 12), prompt(rng, 12)]
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=20)
    )
    assert [len(o) for o in outputs] == [20, 20]
    assert instruments.SCHED_PREEMPTIONS.value > before
    assert_teacher_forced(hf, params, prompts, outputs)


def test_sampled_generation_counts_its_rows_and_pairs():
    hf, params, engine = make_engine(
        hf_over=dict(n_routed_experts=4, num_routed_experts=8)
    )
    rng = np.random.default_rng(4)
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        [prompt(rng, 9), prompt(rng, 30), prompt(rng, 3)],
        SamplingParams(temperature=0.7, top_p=0.9, max_tokens=9),
    )
    assert [len(o) for o in outputs] == [9, 9, 9]
    records = _since(engine, before)
    windows = [r for r in records if r['kind'] == 'decode']
    # every decoded token is one live row of one step; half the router's
    # experts are held, so some pairs are and some are not
    tokens = sum(r['tokens'] for r in windows)
    assert sum(r['state_rows'] for r in windows) == tokens
    assert sum(r['moe_pairs'] for r in windows) == tokens * 2 * 5
    assert 0 < sum(r['moe_pairs_held'] for r in windows) < tokens * 2 * 5
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert prefills and all(r['route'] in ('paged', 'chunk') for r in prefills)


@pytest.mark.parametrize('setting, over', [
    ('enable_prefix_cache', dict(enable_prefix_cache=True)),
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_a_model_with_state_refuses_what_needs_snapshots(setting, over):
    with pytest.raises(ValueError, match=f'{setting} cannot serve a hybrid'):
        make_engine(**over)


def test_a_model_with_state_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = tiny(0)
    with pytest.raises(ValueError, match='mesh cannot serve a hybrid'):
        LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


def test_no_line_of_the_engine_names_the_family():
    from pathlib import Path

    import distllm_tpu.generate.engine as engine_package

    for path in Path(engine_package.__file__).parent.glob('*.py'):
        text = path.read_text().lower()
        assert 'solar' not in text and 'kda' not in text, path.name


def test_warmup_compiles_every_shape_and_serves_after():
    hf, params, engine = make_engine(max_model_len=32, max_num_seqs=2)
    engine.warmup()
    p = prompt(np.random.default_rng(6), 10)
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=5))
    assert_teacher_forced(hf, params, [p], out)


@pytest.fixture(scope='module')
def probe_check():
    """The cell's own check (``drivers/solar_open2_closed``: the greedy
    calls through ``LLMEngine``, then the reference) at toy size, on an
    engine built as an arm of ``scripts/probe_solar_open2_reference.py``
    says; returns the arm's result line."""
    import functools
    import io
    import json
    from contextlib import redirect_stdout
    from pathlib import Path

    from solar_open2_toy import load_probe

    root = Path(__file__).resolve().parent.parent
    probe = load_probe()
    model = json.loads((
        root / 'benchmarks/tests/rehearsal_solar_open2/configs/tiny-solar-open2.json'
    ).read_text())

    @functools.cache
    def run(arm):
        out = io.StringIO()
        with redirect_stdout(out):
            probe['check'](model, [3000000123], [arm])
        return json.loads(out.getvalue().strip().splitlines()[-1])

    return run


# float32 on both sides at toy size: the right program reads 1e-6, so a
# fault shows in the reading the arm is about whatever the chip's limits are.
# ``kda_equal_operand_error`` is the two forms of the program's recurrence
# against the reference's from equal operands: the reading that holds the
# recurrence to its precision.
@pytest.mark.parametrize('arm, by, over', [
    ('state_bf16', 'kda_equal_operand_error', 1e-3),
    ('beta_half', 'kda_state_error', 0.1),
])  # the other arms' faults: tests/test_solar_open2.py, on logits
def test_the_cells_check_reads_a_wrong_program(probe_check, arm, by, over):
    right = probe_check('program')
    assert right['correct'] is True
    assert max(right['kda_state_error']) < 1e-5 and right['kda_equal_operand_error'] < 1e-5
    assert len(right['kda_state_error']) == 3  # every KDA layer
    wrong = probe_check(arm)
    assert np.max(wrong[by]) > over
    if arm == 'state_bf16':  # what the stored bits say holds at any size
        assert wrong['correct'] is False and wrong['kda_state_bf16_share'] == 1.0
    if arm != 'state_bf16':  # nothing of the forms: equal operands, equal states
        assert wrong['kda_equal_operand_error'] < 1e-5
