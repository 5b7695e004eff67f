"""``LLMEngine`` over ``models/ouro.py`` at toy widths on the CPU (the model
itself: ``test_ouro.py``): greedy generation against the plain reference's
full forward pass, what a finished request left in the planes of the first
and of the last pass, the exit rule below the published threshold through
the decode window's counts, slots turned over, preemption and re-admission,
what a looped model refuses, warm-up, and the records' fields."""

import jax
import numpy as np
import pytest

from benchmarks import reference_ouro as ref
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from ouro_toy import BLOCK, NoTokenizer, make_engine, prompt, tiny


def assert_teacher_forced(hf, params, prompts, outputs, limit=1e-3, **kw):
    """Every generated token is the reference's greedy token given the same
    history, or within ``limit`` standard deviations of it (float32 on both
    sides: a tie is the one way to differ)."""
    for p, o in zip(prompts, outputs):
        tokens = list(p) + list(o)[:-1]
        at = len(p) - 1 + np.arange(len(o))[None]
        logits = ref.forward(params, hf, np.asarray(tokens)[None], at, **kw)['logits']
        assert ref.token_gaps(logits, [o]).max() < limit


def _records(engine, before):
    return engine.flight.snapshot()[before - engine.flight.total_recorded:]


@pytest.mark.parametrize('n', [1, 3, 8, 20])
def test_generate_ids_is_the_references_greedy(n):
    hf, params, engine = make_engine()
    p = prompt(np.random.default_rng(n), n)
    before = engine.flight.total_recorded
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=7))
    assert len(out[0]) == 7
    assert_teacher_forced(hf, params, [p], out)
    assert engine.telemetry['loop_window_form'] == 'passes_rolled_layers_unrolled'
    assert engine.kv.pool_shape[0] == 12  # one pool of T * L planes
    records = _records(engine, before)
    windows = [r for r in records if r['kind'] == 'decode']
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert windows and prefills
    for r in windows + prefills:
        assert r['loop_passes'] == 4 and r['kv_planes'] == 12
    assert all(r['route'] in ('paged', 'chunk') for r in prefills)
    # at the published threshold every decoded token's head reads the last pass
    exits = np.sum([r['loop_exit_pass'] for r in windows], axis=0)
    np.testing.assert_array_equal(exits, [0, 0, 0, sum(r['tokens'] for r in windows)])
    if n == 1:  # the step records price four sweeps of the stack
        stack = sum(leaf.size for leaf in jax.tree.leaves(params['layers']))
        rest = sum(leaf.size for leaf in jax.tree.leaves(params)) - stack
        assert engine._cost_model.n_params == 4 * stack + rest


def test_the_planes_a_finished_request_left_are_the_references():
    """What the benchmark's content limits read: the ``request`` record
    names the first and last block a request held; the pool keeps what it
    held, in a plane of the first pass and in one of the last."""
    hf, params, engine = make_engine()
    rng = np.random.default_rng(3)
    prompts = [prompt(rng, 6), prompt(rng, 19), prompt(rng, 11)]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=13)
    )
    records = sorted(
        (r for r in _records(engine, before) if r['kind'] == 'request'),
        key=lambda r: r['request_id'],
    )
    planes = (0, 2, 9, 11)  # pass 0's first and last layer, pass 3's
    for p, o, r in zip(prompts, outputs, records):
        fed = list(p) + list(o)[:-1]
        want = ref.forward(params, hf, np.asarray(fed)[None], [[0]], planes=planes)
        at = (len(fed) - 1) // BLOCK * BLOCK
        for plane in planes:
            for pool, rows in zip((engine.kv.k, engine.kv.v), want['planes'][plane]):
                first = np.asarray(pool[plane][np.asarray([r['kv_first_block']])])[0]
                assert ref.content_error(first, rows[0, :BLOCK]) < 1e-5
                tail = np.asarray(pool[plane][np.asarray([r['kv_tail_block']])])[0]
                assert ref.content_error(tail[:len(fed) - at], rows[0, at:]) < 1e-5


@pytest.mark.parametrize('threshold', [0.3, 0.6])
def test_tokens_leave_early_below_the_published_threshold(threshold):
    """The engine follows the exit rule at any threshold: greedy tokens are
    the reference's at that threshold, and the windows count, pass by pass,
    the decoded tokens whose head read it."""
    hf, params, engine = make_engine(
        hf_over={'early_exit_threshold': threshold}
    )
    rng = np.random.default_rng(11)
    prompts = [prompt(rng, 9), prompt(rng, 14)]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=11)
    )
    assert_teacher_forced(hf, params, prompts, outputs)
    windows = [r for r in _records(engine, before) if r['kind'] == 'decode']
    exits = np.sum([r['loop_exit_pass'] for r in windows], axis=0)
    want = np.zeros(4, int)
    for p, o in zip(prompts, outputs):
        fed = np.asarray(list(p) + list(o)[:-1])[None]
        passes = ref.forward(params, hf, fed, [[0]])['exit_pass'][0]
        want += np.bincount(passes[len(p):], minlength=4)  # the decoded tokens'
    np.testing.assert_array_equal(exits, want)
    assert (want[:3] > 0).sum() >= 2  # tokens did leave early


def test_more_prompts_than_slots_turn_every_slot_over():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(1)
    prompts = [prompt(rng, n) for n in (5, 19, 11, 30, 7, 3, 14, 9, 2)]
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=10)
    )
    assert_teacher_forced(hf, params, prompts, outputs)


def test_a_preempted_request_is_admitted_again_with_the_same_logits():
    # 10 usable blocks of 4 tokens; two rows of 12 + 20 tokens need 16.
    from distllm_tpu.observability import instruments

    hf, params, engine = make_engine(num_blocks=11, max_num_seqs=2)
    # As if finished requests had used none of their budgets: the
    # look-ahead then admits both rows, and the pool runs short under them.
    engine._ewma['budget_use'] = 0.0
    before = instruments.SCHED_PREEMPTIONS.value
    rng = np.random.default_rng(3)
    prompts = [prompt(rng, 12), prompt(rng, 12)]
    sampling = SamplingParams(temperature=0.0, max_tokens=20)
    outputs = engine.generate_ids(prompts, sampling)
    assert [len(o) for o in outputs] == [20, 20]
    assert instruments.SCHED_PREEMPTIONS.value > before
    assert_teacher_forced(hf, params, prompts, outputs)
    # ... and the tokens of an engine whose pool never ran short
    _, _, roomy = make_engine(max_num_seqs=2)
    assert roomy.generate_ids(prompts, sampling) == outputs


def test_sampled_generation_runs_to_its_budget():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(4)
    outputs = engine.generate_ids(
        [prompt(rng, 9), prompt(rng, 30), prompt(rng, 3)],
        SamplingParams(temperature=0.5, top_p=0.95, max_tokens=9),
    )
    assert [len(o) for o in outputs] == [9, 9, 9]


@pytest.mark.parametrize('setting, over', [
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_a_looped_model_refuses_what_it_does_not_serve(setting, over):
    with pytest.raises(ValueError, match=f'{setting} cannot serve a looped model'):
        make_engine(**over)


def test_a_looped_model_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = tiny(0)
    with pytest.raises(ValueError, match='mesh cannot serve a looped model'):
        LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


def test_no_line_of_the_engine_names_the_family():
    from pathlib import Path

    import distllm_tpu.generate.engine as engine_package

    for path in Path(engine_package.__file__).parent.glob('*.py'):
        assert 'ouro' not in path.read_text().lower(), path.name


def test_warmup_compiles_every_shape_and_serves_after():
    hf, params, engine = make_engine(max_model_len=32, max_num_seqs=2)
    engine.warmup()
    p = prompt(np.random.default_rng(6), 10)
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=5))
    assert_teacher_forced(hf, params, [p], out)
