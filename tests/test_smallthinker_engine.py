"""``LLMEngine`` over ``models/smallthinker.py`` at toy widths on the CPU (the
model itself: ``test_smallthinker.py``): greedy generation through chunked
prefill and decode across the window against the plain reference's full
forward pass, the two new counters of the windowed pool on the records, what
a finished request left in the pages of both groups (through the cell's own
check), preemption and re-admission, what the family refuses, warm-up, and
the step's price."""

import jax
import numpy as np
import pytest

from benchmarks import reference_smallthinker as ref
from benchmarks.drivers import smallthinker_closed
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from smallthinker_toy import BLOCK, WINDOW, NoTokenizer, make_engine, prompt, tiny

GREEDY = dict(temperature=0.0)


def _teacher_forced_gaps(hf, params, prompts, outputs):
    gaps = []
    for p, o in zip(prompts, outputs):
        ids = np.asarray([list(p) + list(o)[:-1]])
        at = len(p) - 1 + np.arange(len(o))[None]
        gaps.append(float(ref.smallthinker_token_gaps(
            params, hf, ids, at, [o]
        )[0].max()))
    return gaps


def _records(engine, before):
    return engine.flight.snapshot()[before - engine.flight.total_recorded:]


@pytest.mark.parametrize('backend', ['xla', 'interpret'])
def test_engine_tokens_are_the_references_under_across_and_past_the_window(backend):
    """Rows that stay under the window of 24, cross it while they decode,
    and start past it, prefilled in chunks of 8."""
    hf, params, engine = make_engine(attn_backend=backend)
    rng = np.random.default_rng(1)
    lengths = (5, 17, 61) if backend == 'xla' else (17,)
    prompts = [prompt(rng, n) for n in lengths]
    outputs = engine.generate_ids(prompts, SamplingParams(max_tokens=14, **GREEDY))
    assert [len(o) for o in outputs] == [14] * len(prompts)
    assert max(_teacher_forced_gaps(hf, params, prompts, outputs)) < 1e-3
    assert engine.window_blocks.num_held == 0  # everything went back
    assert engine.window_blocks.freed_total > 0
    assert engine.telemetry['attn_backend'] == backend


def test_records_carry_the_windowed_pools_size_and_the_rows_under_the_window():
    hf, params, engine = make_engine(
        hf_over=dict(moe_num_primary_experts=4, num_routed_experts=8)
    )
    pools = engine.telemetry['kv_pools']
    assert (pools['full']['layers'], pools['window']['layers']) == (2, 6)
    assert pools['window']['window'] == WINDOW
    before = engine.flight.total_recorded
    rng = np.random.default_rng(2)
    # 9 + 10 stays under 24; 20 + 10 crosses it; 50 is past it.
    outputs = engine.generate_ids(
        [prompt(rng, n) for n in (9, 20, 50)],
        SamplingParams(temperature=0.7, top_p=0.9, max_tokens=10),
    )
    assert [len(o) for o in outputs] == [10] * 3
    steps = [r for r in _records(engine, before) if r['kind'] in ('prefill', 'decode')]
    assert steps and all(
        r['kv_window_pool_blocks'] == engine.window_blocks.num_blocks - 1
        and 0 <= r['rows_under_window'] <= r['batch']
        and r['kv_blocks_window'] <= r['kv_window_pool_blocks'] for r in steps
    )
    decodes = [r for r in steps if r['kind'] == 'decode' and r['batch'] == 3]
    # Two rows under the window at first, one once the second has crossed.
    assert [r['rows_under_window'] for r in decodes][0] == 2
    assert [r['rows_under_window'] for r in decodes][-1] == 1
    # 8 layers x 3 picks a token; half the experts are held.
    windows = [r for r in steps if r['kind'] == 'decode']
    assert sum(r['moe_pairs'] for r in windows) == 24 * sum(r['tokens'] for r in windows)
    assert all(0 < r['moe_pairs_held'] < r['moe_pairs'] for r in windows)
    assert {r['moe_form'] for r in windows} == {'dense'}


def test_request_records_name_the_blocks_of_both_groups_the_check_reads():
    """The cell's own page check over what finished requests left: layer
    0's K and V in the full group's first and tail block, layer 1's in the
    window group's first HELD block (the window's lower edge) and tail
    block, against the reference's keys and values; pages rolled by a slot
    read as wrong."""
    hf, params, engine = make_engine()
    rng = np.random.default_rng(8)
    prompts = [prompt(rng, n) for n in (40, 13, 25)]  # under, across, past
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(prompts, SamplingParams(max_tokens=12, **GREEDY))
    records = sorted(
        (r for r in _records(engine, before) if r['kind'] == 'request'),
        key=lambda r: r['request_id'],
    )
    assert len(records) == 3
    pools = {'full': engine.kv, 'window': engine.window_kv}
    for record, p, out in zip(records, prompts, outputs):
        tokens = list(p) + list(out)[:-1]
        ends = smallthinker_closed._ends(record, BLOCK)
        first_held = ends['window'][1][0]
        assert first_held <= max(0, len(tokens) - WINDOW) and min(ends['window'][0]) >= 1
        if len(tokens) > WINDOW + BLOCK:
            assert first_held > 0  # blocks behind the window went back
        pages = {
            group: (at, *(
                np.asarray(side[0][np.asarray(blocks)], np.float32)
                for side in (pools[group].k, pools[group].v)
            ))
            for group, (blocks, at) in ends.items()
        }
        _, kept = ref.smallthinker_logits(
            params, hf, [tokens], [[len(tokens) - 1]], keep=(0, 1),
            fields=('k', 'v'),
        )
        errors = smallthinker_closed._page_errors(pages, kept[0], len(tokens))
        assert max(errors.values()) < 1e-5
        rolled = {
            g: (at, np.roll(k, 1, axis=1), np.roll(v, 1, axis=1))
            for g, (at, k, v) in pages.items()
        }
        wrong = smallthinker_closed._page_errors(rolled, kept[0], len(tokens))
        assert min(wrong.values()) > 0.5


def test_a_preempted_request_is_admitted_again_and_gives_the_same_tokens():
    from distllm_tpu.observability import instruments

    rng = np.random.default_rng(4)
    prompts = [prompt(rng, 30), prompt(rng, 30)]
    params_ = SamplingParams(max_tokens=20, **GREEDY)
    _, _, roomy = make_engine(max_num_seqs=2)
    want = roomy.generate_ids(prompts, params_)
    # 18 usable blocks of 4 tokens; two rows of 30 + 20 tokens need 26.
    hf, params, tight = make_engine(num_blocks=19, max_num_seqs=2)
    tight._ewma['budget_use'] = 0.0  # the look-ahead admits both rows
    before = instruments.SCHED_PREEMPTIONS.value
    got = tight.generate_ids(prompts, params_)
    assert instruments.SCHED_PREEMPTIONS.value > before
    assert got == want
    assert max(_teacher_forced_gaps(hf, params, prompts, got)) < 1e-3
    assert tight.window_blocks.num_held == 0


def test_add_request_and_step_finish_rows_in_their_own_windows():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(3)
    ids = [
        engine.add_request(prompt(rng, n), SamplingParams(max_tokens=m, **GREEDY))
        for n, m in ((30, 3), (7, 17))
    ]
    while engine.has_unfinished:
        engine.step()
    assert [len(engine._finished[r].output_ids) for r in ids] == [3, 17]


def test_warmup_compiles_the_two_group_shapes_and_serves_after():
    hf, params, engine = make_engine(max_model_len=32, max_num_seqs=2)
    engine.warmup()
    p = prompt(np.random.default_rng(6), 20)
    out = engine.generate_ids([p], SamplingParams(max_tokens=5, **GREEDY))
    assert max(_teacher_forced_gaps(hf, params, [p], out)) < 1e-3


@pytest.mark.parametrize('setting, over', [
    ('enable_prefix_cache', dict(enable_prefix_cache=True)),
    ('host_kv_tier_bytes', dict(enable_prefix_cache=True, host_kv_tier_bytes=1 << 20)),
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_the_family_refuses_by_name_what_a_windowed_group_cannot_serve(setting, over):
    with pytest.raises(
        ValueError, match=f'{setting} cannot serve a model with a windowed'
    ):
        make_engine(**over)


def test_the_family_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = tiny(0)
    with pytest.raises(ValueError, match='mesh cannot serve a model with a windowed'):
        LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


def test_the_cost_model_prices_a_step_of_held_experts_without_the_embedding():
    """Bytes: everything held but the embedding (gathered by row). FLOPs:
    of the banks only the share a token reaches, 3 of the 8 the router
    ranks, whatever is held."""
    hf, params, engine = make_engine(
        hf_over=dict(moe_num_primary_experts=4, num_routed_experts=8)
    )
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    banks = sum(size(params['sparse'][n]) for n in ('gate', 'up', 'down'))
    rest = size(params) - size(params['embed']) - banks
    model = engine._cost_model
    assert model.weight_bytes == 4 * (rest + banks)  # float32 toy
    assert model.n_params == pytest.approx(rest + banks * 3 / 8)
