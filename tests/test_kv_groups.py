"""Cache groups (docs/serving.md): what a model's ``cache_spec()`` declares,
the windowed group's allocator (``kv_cache.WindowBlocks``), and the engine
over a toy ``laguna``: a full-context group whose blocks are the
scheduler's, a windowed group that holds only what a query still sees.
"""

import jax
import numpy as np
import pytest

from benchmarks import reference_laguna as ref
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.generate.engine.kv_cache import WindowBlocks, window_bound
from distllm_tpu.generate.engine.scheduler import BudgetRow, decode_budget_fits
from distllm_tpu.models import common
from laguna_toy import BLOCK, WINDOW, NoTokenizer, make_engine, prompt, tiny


# ------------------------------------------------------------ the allocator
def _drive(blocks, rid, length, chunk, decode, step=1):
    """A sequence as the engine drives it: prefill in ``chunk``-token
    spans, each covered then trimmed, then ``decode`` tokens in windows of
    ``step``; yields ``(span, held while it is dispatched)``."""
    for start in range(0, length, chunk):
        ntok = min(chunk, length - start)
        blocks.cover(rid, start, start + ntok)
        yield ntok, blocks.held(rid)
        blocks.trim_behind(rid, start + ntok)
    for pos in range(length, length + decode, step):
        blocks.cover(rid, pos, pos + step)
        yield step, blocks.held(rid)


@pytest.mark.parametrize('length, chunk, step', [
    (200, 16, 1), (203, 8, 4), (64, 32, 8), (11, 16, 2),
])
def test_a_windowed_sequence_never_holds_more_than_its_bound(length, chunk, step):
    blocks = WindowBlocks(64, BLOCK, WINDOW)
    for span, held in _drive(blocks, 7, length, chunk, 40, step):
        assert held <= blocks.bound(span)
    # By now everything behind the window went back: the row holds what
    # the window and one decode window touch, however long it has run.
    assert blocks.held(7) <= blocks.bound(step)
    assert blocks.num_free + blocks.held(7) == 63


def test_freed_ids_return_and_are_held_by_one_sequence_at_a_time():
    blocks = WindowBlocks(2 * 9 + 1, BLOCK, WINDOW)  # two rows' bounds
    a, b = _drive(blocks, 1, 90, 8, 30), _drive(blocks, 2, 70, 8, 50)
    for _ in zip(a, b):
        rows = [set(blocks._rows[r].values()) for r in (1, 2)]
        assert not rows[0] & rows[1] and 0 not in rows[0] | rows[1]
    assert blocks.freed_total > 30  # ids went round more than once
    blocks.release(1)
    blocks.release(2)
    assert blocks.num_free == 18 and blocks.num_held == 0
    assert sorted(blocks._free) == list(range(1, 19))


def test_table_entries_behind_the_window_are_the_trash_block():
    blocks = WindowBlocks(32, BLOCK, WINDOW)
    for _ in _drive(blocks, 3, 50, 8, 0):
        pass
    blocks.cover(3, 50, 51)  # the next query: position 50 sees 39..50
    row = blocks.table_row(3, np.zeros((20,), np.int32))
    first = (50 - WINDOW + 1) // BLOCK
    assert not row[:first].any() and row[first:50 // BLOCK + 1].all()
    assert not row[50 // BLOCK + 1:].any()


def test_a_pool_that_runs_short_says_it_is_a_bug():
    blocks = WindowBlocks(4, BLOCK, WINDOW)
    with pytest.raises(RuntimeError, match='windowed KV pool exhausted'):
        blocks.cover(1, 0, 40)


def test_decode_budget_fits_a_pool_of_constant_demand():
    """The function the scheduler's pool is asked with, asked of the
    windowed pool: rows that grow to a bound and no further."""
    bound = window_bound(WINDOW, BLOCK, 4)  # 5 blocks
    below = bound * BLOCK - 1  # a step from the bound, and one step to go
    held = [BudgetRow(below, 1, h, 0) for h in (5, 3, 0)]
    assert decode_budget_fits(held, 7, BLOCK, 4)  # 0 + 2 + 5
    assert not decode_budget_fits(held, 6, BLOCK, 4)
    # A row inside its prefill dispatch holds more than the bound: no growth.
    assert decode_budget_fits([BudgetRow(below, 1, 9, 0)], 0, BLOCK, 4)


# -------------------------------------------------------------- the engine
def _teacher_forced_gaps(hf, params, prompts, outputs):
    gaps = []
    for p, o in zip(prompts, outputs):
        ids = np.asarray([list(p) + list(o)[:-1]])
        at = len(p) - 1 + np.arange(len(o))[None]
        gaps.append(ref.token_gaps(ref.laguna_logits(params, hf, ids, at), [o]).max())
    return gaps


@pytest.mark.parametrize('backend', ['xla', 'interpret'])
def test_engine_tokens_are_the_references_past_window_and_chunk(backend):
    hf, params, engine = make_engine(attn_backend=backend)
    rng = np.random.default_rng(1)
    prompts = [prompt(rng, n) for n in ((61, 5, 33) if backend == 'xla' else (41,))]
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=14)
    )
    assert [len(o) for o in outputs] == [14] * len(prompts)
    assert max(_teacher_forced_gaps(hf, params, prompts, outputs)) < 1e-3
    assert engine.window_blocks.num_held == 0  # everything went back
    assert engine.window_blocks.freed_total > 0


def test_records_and_telemetry_carry_the_groups():
    hf, params, engine = make_engine(
        hf_over=dict(num_experts=4, num_routed_experts=8)
    )
    pools = engine.telemetry['kv_pools']
    n_kv, head_dim = engine.kv.shape[3:]
    assert pools['full'] == {
        'layers': 2, 'window': None, 'blocks': 64, 'bytes': engine.kv.hbm_bytes,
        'block_shape': [engine.kv.block_size, n_kv * head_dim],  # as stored
    }
    assert pools['window']['layers'] == 4 and pools['window']['window'] == WINDOW
    assert pools['window']['blocks'] == engine.window_blocks.num_blocks
    # each group's pool ONE stacked array over its layers
    assert engine.kv.k_pool.shape == engine.kv.pool_shape
    assert engine.window_kv.k_pool.shape == engine.window_kv.pool_shape
    # K and V planes both, in both groups (no group declares a latent row).
    assert len(engine.kv.v_pool) == 2 and len(engine.window_kv.v_pool) == 4
    assert not engine.kv.latent and not engine.window_kv.latent
    assert len(engine.kv.k) == 2 and len(engine.window_kv.v) == 4  # the host's view
    before = engine.flight.total_recorded
    rng = np.random.default_rng(2)
    outputs = engine.generate_ids(
        [prompt(rng, 50), prompt(rng, 9)],
        SamplingParams(temperature=0.7, top_p=0.9, max_tokens=10),
    )
    assert [len(o) for o in outputs] == [10, 10]
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    steps = [r for r in records if r['kind'] in ('prefill', 'decode')]
    assert steps and all(
        r['kv_blocks_full'] == r['kv_blocks'] and r['kv_blocks_window'] >= 1
        and r['window_blocks_freed'] >= 0 for r in steps
    )
    decodes = [r for r in steps if r['kind'] == 'decode']
    assert all(0 < r['moe_pairs_held'] < r['moe_pairs'] for r in decodes)
    # 5 sparse layers x 2 picks a token: every decode token routes 10 pairs.
    assert sum(r['moe_pairs'] for r in decodes) == 10 * sum(
        r['tokens'] for r in decodes
    )
    # Past the window the group holds less than the context fills.
    late = [r for r in decodes if r['kv_blocks_full'] > 2 * r['kv_blocks_window']]
    assert late and sum(r['window_blocks_freed'] for r in steps) > 5


def test_request_records_name_the_blocks_the_check_reads():
    """``kv_first_block`` and ``kv_tail_block``: the full group's block a
    finished request's first positions are in, and the one that holds the
    last position it wrote, also where that position fills its block (the
    scheduler then already holds the next, empty one). Layer 0's K and V
    there are what the reference makes of the tokens alone."""
    from benchmarks.drivers import laguna_closed

    hf, params, engine = make_engine()
    rng = np.random.default_rng(8)
    prompts = [prompt(rng, n) for n in (40, 13, 25)]  # 25 + 11 fills a block
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=12)
    )
    records = sorted(
        (r for r in engine.flight.snapshot()[before - engine.flight.total_recorded:]
         if r['kind'] == 'request'), key=lambda r: r['request_id'],
    )
    assert len(records) == 3
    for record, p, out in zip(records, prompts, outputs):
        ends = np.asarray([record['kv_first_block'], record['kv_tail_block']])
        assert ends.min() >= 1  # never the trash block
        pages = [np.asarray(pool[0][ends]) for pool in (engine.kv.k, engine.kv.v)]
        assert laguna_closed._page_error(params, hf, p, out, *pages) < 1e-5
        wrong = [np.roll(page, 1, axis=1) for page in pages]
        assert laguna_closed._page_error(params, hf, p, out, *wrong) > 0.5


def test_rows_of_different_lengths_finish_in_different_windows():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(3)
    ids = [
        engine.add_request(prompt(rng, n), SamplingParams(temperature=0.0, max_tokens=m))
        for n, m in ((30, 3), (7, 17))
    ]
    finished_at = {}
    steps = 0
    while engine.has_unfinished:
        engine.step()
        steps += 1
        for rid in ids:
            if rid in engine._finished and rid not in finished_at:
                finished_at[rid] = steps
    assert finished_at[ids[0]] < finished_at[ids[1]]
    assert [len(engine._finished[r].output_ids) for r in ids] == [3, 17]


def test_a_preempted_request_is_admitted_again_and_gives_the_same_tokens():
    from distllm_tpu.observability import instruments

    rng = np.random.default_rng(4)
    prompts = [prompt(rng, 30), prompt(rng, 30)]
    params_ = SamplingParams(temperature=0.0, max_tokens=20)
    _, _, roomy = make_engine(max_num_seqs=2)
    want = roomy.generate_ids(prompts, params_)
    # 18 usable blocks of 4 tokens; two rows of 30 + 20 tokens need 26.
    hf, params, tight = make_engine(num_blocks=19, max_num_seqs=2)
    # As if finished requests had used none of their budgets: the
    # look-ahead then admits both rows, and the pool runs short under them.
    tight._ewma['budget_use'] = 0.0
    before = instruments.SCHED_PREEMPTIONS.value
    got = tight.generate_ids(prompts, params_)
    assert instruments.SCHED_PREEMPTIONS.value > before
    assert got == want
    assert tight.window_blocks.num_held == 0


def test_the_windowed_pool_never_makes_a_request_wait():
    """The engine sizes the windowed pool from what it serves with (the
    trash block, every slot at its constant, one prefill dispatch), so
    admission asks nothing of it: all four slots decode together, nothing
    is deferred or preempted for it, and it is never more than full."""
    hf, params, engine = make_engine()
    blocks = engine.window_blocks
    assert blocks.num_blocks == (
        1 + 4 * engine._window_decode_bound + engine._window_prefill_reserve
    )
    least_free = [blocks.num_free]
    cover = blocks.cover

    def watched(*args):
        freed = cover(*args)
        least_free.append(blocks.num_free)
        return freed

    blocks.cover = watched
    rng = np.random.default_rng(5)
    prompts = [prompt(rng, n) for n in (40, 44, 36, 50, 12)]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=12)
    )
    assert [len(o) for o in outputs] == [12] * 5
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    assert max(r['batch'] for r in records if r['kind'] == 'decode') == 4
    assert not [r for r in records if r['kind'] == 'preempt']
    assert min(least_free) >= 0 and blocks.num_held == 0
    assert max(_teacher_forced_gaps(hf, params, prompts, outputs)) < 1e-3


@pytest.mark.parametrize('slots,chunk,steps', [(1, 8, 4), (4, 8, 4), (3, 16, 2)])
def test_the_windowed_pool_is_sized_by_what_the_engine_serves_with(
    slots, chunk, steps
):
    """No option sizes the pool: it follows ``max_num_seqs``, the window,
    the block size, ``decode_steps`` and the prefill buckets, and holds at
    least one request's prefill dispatch beside every other slot decoding."""
    _, _, engine = make_engine(
        max_num_seqs=slots, prefill_chunk_tokens=chunk, decode_steps=steps
    )
    bound = window_bound(WINDOW, BLOCK, steps)
    assert engine._window_decode_bound == bound
    assert engine._window_prefill_reserve >= window_bound(WINDOW, BLOCK, chunk) - bound
    assert engine.window_blocks.num_blocks == (
        1 + slots * bound + engine._window_prefill_reserve
    )
    assert 'num_window_blocks' not in type(engine.config).model_fields


def test_warmup_compiles_the_two_group_shapes_and_serves_after():
    hf, params, engine = make_engine(max_model_len=32, max_num_seqs=2)
    engine.warmup()
    p = prompt(np.random.default_rng(6), 20)
    out = engine.generate_ids([p], SamplingParams(temperature=0.0, max_tokens=5))
    assert max(_teacher_forced_gaps(hf, params, [p], out)) < 1e-3


# (f) each refusal raises, naming the setting and its reason.
@pytest.mark.parametrize('setting, over', [
    ('enable_prefix_cache', dict(enable_prefix_cache=True)),
    ('host_kv_tier_bytes', dict(enable_prefix_cache=True, host_kv_tier_bytes=1 << 20)),
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_a_windowed_group_refuses_what_cannot_be_right_yet(setting, over):
    with pytest.raises(
        ValueError, match=f'{setting} cannot serve a model with a windowed'
    ):
        make_engine(**over)


def test_a_windowed_group_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = tiny(0)
    with pytest.raises(ValueError, match='mesh cannot serve a model with a windowed'):
        LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


@pytest.mark.parametrize('groups', [
    (('near', 8), ('far', 16)),  # no full-context group first
    ((None, None), ('near', 8), ('far', 16)),  # windows of two sizes
])
def test_groups_the_engine_has_no_allocator_for_are_refused(groups):
    hf, cfg, params = tiny(0)

    class Mixed(type(cfg)):
        def cache_spec(self):
            return common.CacheSpec(
                paged=tuple(
                    common.PagedGroup(name or 'full', 2, window)
                    for name, window in groups
                ),
                programs='distllm_tpu.models.laguna',
            )

    with pytest.raises(ValueError, match='windows of several sizes'):
        LLMEngine(
            Mixed(**cfg.model_dump()), params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2),
        )


def test_every_decoder_config_declares_its_cache():
    from distllm_tpu.models import decoder_families

    for name, (cls, module) in decoder_families().items():
        spec = cls().cache_spec()
        assert spec.paged and spec.paged[0].window is None, name
        programs = __import__(spec.programs, fromlist=['x'])
        assert hasattr(programs, 'prefill_paged') and hasattr(programs, 'decode_loop')
        assert (spec.state is None) == (not hasattr(cls(), 'state_spec')), name
