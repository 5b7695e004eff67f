"""``LLMEngine`` over ``models/falcon_h1.py`` at toy widths on the CPU (the
model itself: ``test_falcon_h1.py``): greedy generation against the plain
reference's argmax, what a finished request leaves in EVERY layer's state
slot and pages, a slot reused, preemption and re-admission, the refusals of a
model with state (granite's toy, lfm2's and this one by one test), warm-up,
the ``state_rows`` counter, and the cell's own check on wrong programs."""

import jax
import numpy as np
import pytest

from benchmarks import reference_falcon_h1 as ref
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from falcon_h1_toy import BLOCK, NoTokenizer, make_engine, prompt, tiny


def assert_teacher_forced(hf, params, prompts, outputs, limit=1e-3):
    """Every generated token is the reference's greedy token given the same
    history, or within ``limit`` standard deviations of it (float32 on both
    sides: a tie is the one way to differ)."""
    for p, o in zip(prompts, outputs):
        tokens = list(p) + list(o)[:-1]
        at = len(p) - 1 + np.arange(len(o))[None]
        logits = ref.falcon_h1_logits(params, hf, np.asarray(tokens)[None], at)
        assert ref.token_gaps(logits, [o]).max() < limit


@pytest.mark.parametrize('n, backend', [
    (1, 'xla'), (2, 'xla'), (3, 'xla'), (8, 'xla'), (20, 'xla'),
])
def test_generate_ids_is_the_references_greedy(n, backend):
    hf, params, engine = make_engine(attn_backend=backend)
    p = prompt(np.random.default_rng(n), n)
    before = engine.flight.total_recorded
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=7))
    assert len(out[0]) == 7
    assert_teacher_forced(hf, params, [p], out)
    # Every one of the 3 layers holds pages AND both kinds of state.
    pool = engine.telemetry['state_pool']
    assert pool['slots'] == 4 and pool['bytes_per_slot'] == 3 * (3 * 88 + 4 * 6 * 16) * 4
    assert sorted((leaf['count'], leaf['shape']) for leaf in pool['leaves']) == [
        (3, [3, 88]), (3, [4, 6, 16]),
    ]
    assert engine.telemetry['kv_pools']['kv']['block_shape'] == [BLOCK, 8]
    assert engine.telemetry['kv_pools']['kv']['layers'] == 3
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    (request,) = [r for r in records if r['kind'] == 'request']
    assert {'state_slot', 'kv_first_block', 'kv_tail_block'} <= set(request)
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all({'kv_blocks', 'state_rows'} <= set(r) for r in windows)
    assert all('moe_pairs' not in r for r in windows)
    # one live row: a step of it reads and writes its slot once
    assert sum(r['state_rows'] for r in windows) == sum(r['tokens'] for r in windows) == 6


def test_the_state_and_pages_a_finished_request_left_are_the_references():
    """What the benchmark's content limits read: the ``request`` record
    names the slot and the first and last block a request held; the pools
    keep what they held, in the first layer and in the last."""
    hf, params, engine = make_engine()
    rng = np.random.default_rng(3)
    prompts = [prompt(rng, 6), prompt(rng, 19), prompt(rng, 11)]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=13)
    )
    records = sorted(
        (r for r in engine.flight.snapshot()[before - engine.flight.total_recorded:]
         if r['kind'] == 'request'), key=lambda r: r['request_id'],
    )
    assert sorted(r['state_slot'] for r in records) == [0, 1, 2]
    for p, o, r in zip(prompts, outputs, records):
        fed = list(p) + list(o)[:-1]
        _, held = ref.forward(params, hf, np.asarray(fed)[None], [[0]])
        want_ssm, want_conv, want_k, want_v = held[0]
        state = engine.state_pool.state
        assert ref.content_error(state['ssm'][0][r['state_slot']], want_ssm) < 1e-5
        assert ref.content_error(state['conv'][0][r['state_slot']], want_conv) < 1e-5
        assert np.asarray(state['ssm'][2][r['state_slot']]).any()  # the last layer's too
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


def test_sampled_generation_counts_its_state_rows():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(4)
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        [prompt(rng, 9), prompt(rng, 30), prompt(rng, 3)],
        SamplingParams(temperature=0.7, top_p=0.9, max_tokens=9),
    )
    assert [len(o) for o in outputs] == [9, 9, 9]
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    windows = [r for r in records if r['kind'] == 'decode']
    # every decoded token is one live row of one step
    assert sum(r['state_rows'] for r in windows) == sum(r['tokens'] for r in windows)
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert prefills and all(r['route'] in ('paged', 'chunk') for r in prefills)


def _granite_engine(**over):
    import test_state_pool_granite as granite

    return granite.make_engine(**over)


def _lfm2_engine(**over):
    import lfm2_toy

    return lfm2_toy.make_engine(**over)


# Everything the engine refuses for a model with state, by the same test
# for the three families that have one: a layer that holds pages AND state
# changes none of it.
@pytest.mark.parametrize('family', [make_engine, _lfm2_engine, _granite_engine])
@pytest.mark.parametrize('setting, over', [
    ('enable_prefix_cache', dict(enable_prefix_cache=True)),
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_a_model_with_state_refuses_what_needs_snapshots(family, setting, over):
    with pytest.raises(ValueError, match=f'{setting} cannot serve a hybrid'):
        family(**over)


def test_a_model_with_state_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = tiny(0)
    with pytest.raises(ValueError, match='mesh cannot serve a hybrid'):
        LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


def test_warmup_compiles_every_shape_and_serves_after():
    hf, params, engine = make_engine(max_model_len=32, max_num_seqs=2)
    engine.warmup()
    p = prompt(np.random.default_rng(6), 10)
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=5))
    assert_teacher_forced(hf, params, [p], out)


@pytest.mark.parametrize('shape, itemsize, slab, transfers', [
    ((32, 4096, 14336), 2, 1, 32),  # a stacked kernel: an index a transfer
    ((6, 5120, 21504), 2, 1, 6),
    ((261120, 5120), 2, 6553, 40),  # the embedding: 64 MiB of rows
    ((5120, 261120), 2, 128, 40),  # the head beside it
    ((7, 3), 4, 7, 1),  # all of a small leaf at once
])
def test_a_leaf_of_many_small_rows_migrates_in_slabs(shape, itemsize, slab, transfers):
    """The weight migration's walk through the host (``_migrate_params``):
    one index of the first axis a transfer where that is a MiB or more,
    else slabs of one size that cover every row, the last laid over the end
    of the one before it (261,120 round trips for this family's embedding
    took 545 s of a cold set-up on the chip)."""
    from distllm_tpu.generate.engine.engine import bounce_slabs

    rows, nbytes = shape[0], int(np.prod(shape)) * itemsize
    got, starts = bounce_slabs(rows, nbytes)
    assert got == slab and len(starts) == transfers
    assert starts[0] == 0 and starts[-1] == rows - slab
    covered = np.zeros(rows, bool)
    for i in starts:
        assert 0 <= i <= rows - slab
        covered[i:i + slab] = True
    assert covered.all()
    assert slab == 1 or slab * (nbytes // rows) <= 64 << 20


@pytest.fixture(scope='module')
def probe_check():
    """The cell's own check (``drivers/falcon_h1_closed``: the greedy calls
    through ``LLMEngine``, then the reference) at toy size, on an engine
    built as an arm of ``scripts/probe_falcon_h1_reference.py`` says;
    returns the arm's result line."""
    import functools
    import io
    import json
    import runpy
    import sys
    from contextlib import redirect_stdout
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / 'scripts'))  # it imports its neighbours
    try:
        probe = runpy.run_path(str(root / 'scripts/probe_falcon_h1_reference.py'))
    finally:
        sys.path.remove(str(root / 'scripts'))
    model = json.loads((
        root / 'benchmarks/tests/rehearsal_falcon_h1/configs/tiny-falcon-h1.json'
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
@pytest.mark.parametrize('arm, by, over', [
    ('no_mamba', 'token_gap_mean_std', 0.05),
    ('no_key_multiplier', 'kv_content_error', 0.1),
    ('no_ssm_multipliers', 'ssm_state_error', 0.05),
    ('group0', 'ssm_state_error', 0.1),
    ('no_rope', 'kv_content_error', 0.1),
    ('ssm_bf16', 'ssm_state_error', 1e-3),
    ('int8_kv', 'kv_content_error', 1e-3),
    ('no_zeroing', 'ssm_state_error', 0.01),
])
def test_the_cells_check_reads_a_wrong_program(probe_check, arm, by, over):
    right = probe_check('program')
    assert right['correct'] is True and right[by] < 1e-5
    wrong = probe_check(arm)
    assert wrong[by] > over
    if arm == 'no_zeroing':  # the second holders alone: the last two rows
        by_row = [e[0] for e in wrong['ssm_state_error_by_row']]
        assert max(by_row[:-2]) < 1e-5 < min(by_row[-2:])
