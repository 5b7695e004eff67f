"""``LLMEngine`` over ``models/lfm2.py`` at toy widths on the CPU (the model
itself: ``test_lfm2.py``): greedy generation against the plain reference's
argmax, what a finished request leaves in the state pool and the pages, a
slot reused, preemption and re-admission, the refusals of a model with state
(granite's toy and this one by one test), warm-up, the cost model."""

import jax
import numpy as np
import pytest

from benchmarks import reference_lfm2 as ref
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from lfm2_toy import BLOCK, NoTokenizer, make_engine, prompt, tiny


def assert_teacher_forced(hf, params, prompts, outputs, limit=1e-3):
    """Every generated token is the reference's greedy token given the same
    history, or within ``limit`` standard deviations of it (float32 on both
    sides: a tie is the one way to differ)."""
    for p, o in zip(prompts, outputs):
        tokens = list(p) + list(o)[:-1]
        at = len(p) - 1 + np.arange(len(o))[None]
        logits = ref.lfm2_logits(params, hf, np.asarray(tokens)[None], at)
        assert ref.token_gaps(logits, [o]).max() < limit


@pytest.mark.parametrize('n, backend', [
    (1, 'xla'), (2, 'xla'), (5, 'xla'), (8, 'xla'), (20, 'xla'),
    (20, 'interpret'),
])
def test_generate_ids_is_the_references_greedy(n, backend):
    hf, params, engine = make_engine(attn_backend=backend)
    p = prompt(np.random.default_rng(n), n)
    before = engine.flight.total_recorded
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=7))
    assert len(out[0]) == 7
    assert_teacher_forced(hf, params, [p], out)
    # 4 conv layers x [2, 64] float32 a slot, one kind of leaf.
    assert engine.telemetry['state_pool'] == {
        'slots': 4, 'bytes': 4 * 4 * 2 * 64 * 4, 'bytes_per_slot': 4 * 2 * 64 * 4,
        'leaves': [{'count': 4, 'shape': [2, 64], 'dtype': 'float32'}],
    }
    assert engine.telemetry['kv_pools']['kv']['block_shape'] == [BLOCK, 2 * 16]
    assert engine.telemetry['kv_pools']['kv']['layers'] == 2
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    (request,) = [r for r in records if r['kind'] == 'request']
    assert {'state_slot', 'kv_first_block', 'kv_tail_block'} <= set(request)
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all(
        {'kv_blocks', 'moe_pairs', 'moe_pairs_held'} <= set(r) for r in windows
    )
    # 4 sparse layers x 3 picks a token
    assert sum(r['moe_pairs'] for r in windows) == 12 * sum(
        r['tokens'] for r in windows
    )
    if backend == 'interpret':
        assert all('kv_chunks' in r for r in windows)
        assert engine.telemetry['kv_walk_keys'] == {'kv': 96}


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
        (r for r in engine.flight.snapshot()[before - engine.flight.total_recorded:]
         if r['kind'] == 'request'), key=lambda r: r['request_id'],
    )
    assert sorted(r['state_slot'] for r in records) == [0, 1, 2]
    for p, o, r in zip(prompts, outputs, records):
        fed = list(p) + list(o)[:-1]
        want = ref.first_conv_inputs(params, hf, fed[-2:])
        got = engine.state_pool.state['conv'][0][r['state_slot']]
        assert ref.content_error(got, want) < 1e-5
        want_k, _ = ref.first_attn_kv(params, hf, fed, np.arange(len(fed)))
        first = np.asarray(engine.kv.k[0][np.asarray([r['kv_first_block']])])[0]
        assert ref.content_error(first, want_k[:BLOCK]) < 1e-5
        tail = np.asarray(engine.kv.k[0][np.asarray([r['kv_tail_block']])])[0]
        at = (len(fed) - 1) // BLOCK * BLOCK
        assert ref.content_error(tail[:len(fed) - at], want_k[at:]) < 1e-5


def test_rows_of_different_lengths_finish_at_different_windows():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(1)
    prompts = [prompt(rng, 6), prompt(rng, 19)]
    rids = [
        engine.add_request(prompts[0], SamplingParams(temperature=0.0, max_tokens=3)),
        engine.add_request(prompts[1], SamplingParams(temperature=0.0, max_tokens=11)),
    ]
    got = {rid: [] for rid in rids}
    while engine.has_unfinished:
        for rid, token in engine.step():
            got[rid].append(token)
    outputs = [got[rid] for rid in rids]
    assert [len(o) for o in outputs] == [3, 11]
    assert_teacher_forced(hf, params, prompts, outputs)


def test_a_slot_reused_after_a_longer_holder_starts_from_zero():
    hf, params, engine = make_engine(max_num_seqs=1)
    rng = np.random.default_rng(2)
    sampling = SamplingParams(temperature=0.0, max_tokens=6)
    engine.generate_ids([prompt(rng, 17)], sampling)
    # The one slot now holds the first request's state; the next request
    # takes it, alone and after a call that left the pipeline empty.
    for n in (1, 4, 13):  # one token, one span, and chunks
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


def test_sampled_generation_of_a_share():
    hf, params, engine = make_engine(
        hf_over=dict(num_experts=4, num_routed_experts=8)
    )
    rng = np.random.default_rng(4)
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        [prompt(rng, 9), prompt(rng, 30), prompt(rng, 3)],
        SamplingParams(temperature=0.7, top_p=0.9, max_tokens=9),
    )
    assert [len(o) for o in outputs] == [9, 9, 9]
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all(0 < r['moe_pairs_held'] < r['moe_pairs'] for r in windows)
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert prefills and all(r['route'] in ('paged', 'chunk') for r in prefills)


def _granite_engine(**over):
    import test_state_pool_granite as granite

    return granite.make_engine(**over)


# Everything the engine refuses for a model with state, by the same test
# for both families that have one.
@pytest.mark.parametrize('family', [make_engine, _granite_engine])
@pytest.mark.parametrize('setting, over', [
    ('enable_prefix_cache', dict(enable_prefix_cache=True)),
    ('host_kv_tier_bytes', dict(enable_prefix_cache=True, host_kv_tier_bytes=1 << 20)),
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_a_model_with_state_refuses_what_needs_snapshots(family, setting, over):
    if setting == 'host_kv_tier_bytes':
        setting = 'enable_prefix_cache'  # a tier needs the cache: first refusal
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


def test_roofline_counts_the_parameters_a_token_reaches():
    """A routed bank is priced at the share a token is multiplied by (3 of
    the router's 8 experts here); the dense layers' MLPs, which have no
    router beside them, and the tied embedding whole."""
    from distllm_tpu.observability.roofline import CostModel

    hf, cfg, params = tiny(0, num_experts=4, num_routed_experts=8)
    every = sum(x.size for x in jax.tree.leaves(params))
    banks = sum(params['sparse'][n]['kernel'].size for n in ('gate', 'up', 'down'))
    routed = CostModel.from_params(params, 4, experts_per_token=3)
    assert routed.n_params == pytest.approx(every - banks * (1 - 3 / 8))


@pytest.fixture(scope='module')
def probe_check():
    """The cell's own check (``drivers/lfm2_closed``: the greedy call
    through ``LLMEngine``, then the reference) at toy size, on an engine
    built as an arm of ``scripts/probe_lfm2_reference.py`` says; returns the
    arm's result line."""
    import functools
    import io
    import json
    import runpy
    import sys
    from contextlib import redirect_stdout
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / 'scripts'))  # it imports a neighbour
    try:
        probe = runpy.run_path(str(root / 'scripts/probe_lfm2_reference.py'))
    finally:
        sys.path.remove(str(root / 'scripts'))
    model = json.loads((
        root / 'benchmarks/tests/rehearsal_lfm2/configs/tiny-lfm2.json'
    ).read_text())

    @functools.cache
    def run(arm):
        out = io.StringIO()
        with redirect_stdout(out):
            probe['check'](model, [3000000123], [arm])
        return json.loads(out.getvalue().strip().splitlines()[-1])

    return run


@pytest.mark.parametrize('arm, by, limit', [
    ('one_row', 'kv_content_error_max_row', ref.KV_ROW_LIMIT),
    ('one_row', 'state_content_error', ref.STATE_CONTENT_LIMIT),
    ('state_late', 'state_content_error', ref.STATE_CONTENT_LIMIT),
])
def test_the_cells_check_refuses_a_row_that_holds_anothers(
    probe_check, arm, by, limit
):
    """One scored row of eight that reads another row's slot and pages (a
    wrong slot, a wrong entry of one block table) fails the check by the
    limits on the LARGEST row, where the median over the rows passes it
    over; so does a state carried a token late, in every row."""
    right = probe_check('program')
    assert right['correct'] is True and right[by] < limit / 10
    wrong = probe_check(arm)
    assert wrong['correct'] is False and wrong[by] > 10 * limit
    if arm == 'one_row':
        assert wrong['kv_content_error'] < ref.KV_CONTENT_LIMIT / 10
