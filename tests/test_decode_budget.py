"""Admission by decode budget (scheduler.py "Admission by decode budget"):
the walk itself, the engine's description of a row, and the engine end to
end: a pool that the prompts overfill several times is served without one
preemption, loose budgets do not starve the batch, and recompute preemption
is still the net under an estimate that was low.

CPU, toy sizes; every engine test has a time limit of its own.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

from distllm_tpu.generate.engine.engine import RequestState, SamplingParams
from distllm_tpu.generate.engine.scheduler import (
    BudgetRow,
    NativeScheduler,
    PyScheduler,
    decode_budget_fits,
)
from distllm_tpu.observability import instruments
from distllm_tpu.resilience.faults import get_fault_injector

from test_scheduler import requires_native
from test_step_spans import _engine as _spans_engine
from test_step_spans import _prompts, _since, time_limit


# ------------------------------------------------------------- the walk
# Blocks of 4 tokens, windows of 2 steps. ``short`` ends at 12 tokens (3
# blocks) after 2 windows, ``long`` at 20 (5 blocks) after 6; both hold 2.
SHORT = BudgetRow(length=8, steps=4, held=2, kept=0)
LONG = BudgetRow(length=8, steps=12, held=2, kept=0)


@pytest.mark.parametrize(
    'case, rows, spare, behind, fits',
    [
        # One row grows by 3 blocks to its end.
        ('alone', [LONG], 3, 0, True),
        ('alone, one block short', [LONG], 2, 0, False),
        # Ends together: 3 + 3 blocks at once.
        ('same budgets', [LONG, LONG], 6, 0, True),
        ('same budgets, one block short', [LONG, LONG], 5, 0, False),
        # Staggered: the short row's 3 blocks are back before the long
        # one needs its last two; both ends at once would take 4.
        ('staggered budgets', [SHORT, LONG], 2, 0, True),
        ('staggered budgets, short', [SHORT, LONG], 1, 0, False),
        # In the pipelined loop a finish is seen one dispatch late: the
        # short row still holds 3 blocks while the long one reaches 4.
        ('staggered, a window behind', [SHORT, LONG], 2, 1, False),
        ('staggered, a window behind, fits', [SHORT, LONG], 3, 1, True),
        # A borrowed prefix that another request holds too stays pinned.
        ('shared prefix is kept', [SHORT._replace(kept=2), LONG], 2, 0,
         False),
        ('shared prefix, one kept block', [SHORT._replace(kept=1), LONG], 2,
         0, True),
        # A row already holding more than it will need takes nothing.
        ('over-held row', [LONG._replace(held=5), LONG], 3, 0, True),
        # The window's reservation is capped by the row's own end: 9
        # tokens and one step left is 10 tokens, 3 blocks, not 9 + 2.
        ('capped by its end',
         [BudgetRow(9, 1, 3, 0), BudgetRow(11, 1, 3, 0)], 0, 0, True),
        # A prefill that ends its request (no step left) takes what
        # admission grants (the scheduler's own test) and is gone, with
        # the 2 blocks it held, before the next dispatch: the long row's
        # 3 blocks then take 1 of the spare.
        ('prefill only', [BudgetRow(9, 0, 2, 0), LONG], 1, 0, True),
        ('prefill only, short', [BudgetRow(9, 0, 2, 0), LONG], 0, 0, False),
        # Nothing to carry.
        ('no rows', [], 0, 0, True),
    ],
)
def test_the_walk(case, rows, spare, behind, fits):
    assert decode_budget_fits(rows, spare, 4, 2, behind) is fits, case
    # Order is not part of the question.
    assert decode_budget_fits(rows[::-1], spare, 4, 2, behind) is fits, case


def test_the_walk_agrees_with_a_window_by_window_count():
    """Against the plain reading of the contract: dispatch by dispatch,
    sum what every live row has reserved."""
    rng = np.random.default_rng(0)
    bs, k = 4, 3
    for _ in range(300):
        behind = int(rng.integers(0, 3))
        rows = []
        for _ in range(int(rng.integers(1, 7))):
            length = int(rng.integers(1, 40))
            held = -(-length // bs) + int(rng.integers(0, 2))
            rows.append(BudgetRow(
                length, int(rng.integers(0, 30)), held,
                int(rng.integers(0, held + 1)),
            ))
        spare = int(rng.integers(0, 25))

        def blocks(row, j):
            tokens = row.length + min((j + 1) * k, row.steps)
            return max(row.held, -(-tokens // bs))

        fits = True
        horizon = max(-(-row.steps // k) for row in rows) + behind
        for j in range(horizon + 1):
            used = 0
            for row in rows:
                if j <= -(-row.steps // k) - 1 + behind:
                    used += blocks(row, j) - row.held
                else:
                    used -= row.held - row.kept
            fits = fits and used <= spare
        assert decode_budget_fits(rows, spare, bs, k, behind) is fits


@pytest.mark.parametrize(
    'sched_cls',
    [PyScheduler, pytest.param(NativeScheduler, marks=requires_native)],
)
def test_waiting_head_names_the_next_admission(sched_cls):
    sched = sched_cls(num_blocks=8, block_size=4, max_num_seqs=2)
    assert sched.waiting_head() is None
    sched.add(0, 6)
    sched.add(1, 6)
    sched.add(2, 6)
    assert sched.waiting_head() == 0
    assert sched.admit_next() == 0
    assert sched.waiting_head() == 1 and sched.num_waiting == 2
    assert sched.admit_next() == 1
    # 7 usable blocks: two rows of 2 take 4, growing both by 8 tokens
    # takes 4 more, so the youngest goes back to the FRONT of the queue.
    assert sched.prepare_decode(8) == [1]
    assert sched.waiting_head() == 1
    sched.finish(1)
    assert sched.waiting_head() == 2


# ------------------------------------------------ the engine's description
_engine = functools.partial(_spans_engine, decode_steps=4)


def _admit_all(engine) -> list[int]:
    """What ``_admit`` admits in one pass, without running the prefills."""
    admitted = []
    while (rid := engine._admit_next_evicting()) is not None:
        request = engine._requests[rid]
        request.state = RequestState.RUNNING
        request.admit_tokens = request.num_tokens
        admitted.append(rid)
    return admitted


@time_limit(120)
@pytest.mark.parametrize(
    'case, settings, max_tokens, behind, admitted',
    [
        # 27 usable blocks. A prompt of 40 with 8 to go ends at 48
        # tokens, 12 blocks: two such rows fit, three do not.
        ('tight budgets', dict(num_blocks=28), 8, 0, 2),
        # max_tokens says 2000, max_model_len 48 says 8: the same.
        ('a row at max_model_len', dict(num_blocks=28, max_model_len=48),
         2000, 0, 2),
        # To 64 tokens a row takes 16 blocks: one row, the other waits.
        ('budget beyond the pool', dict(num_blocks=28), 24, 0, 1),
        # Ends together, so a window in flight behind changes nothing...
        ('pipelined, same ends', dict(num_blocks=28), 8, 1, 2),
        # With nothing running the head is always tried, whatever its
        # budget: 11 usable blocks hold its prompt, not its 64 tokens
        # (the second waits on the look-ahead all the same).
        ('nothing running', dict(num_blocks=12), 2000, 0, 1),
    ],
)
def test_rows_are_described_by_their_budgets(
    case, settings, max_tokens, behind, admitted
):
    engine = _engine(**settings)
    engine._windows_behind = behind
    deferred = instruments.SCHED_DEFERRED.labels(reason='decode_budget')
    before = deferred.value
    params = SamplingParams(temperature=0.0, max_tokens=max_tokens)
    for prompt in _prompts((40, 40, 40)):
        engine.add_request(prompt, params)
    assert len(_admit_all(engine)) == admitted, case
    # The next in line waits on the look-ahead, and is counted once
    # however often it is asked about.
    assert engine._stats['budget_deferrals'] == 1
    assert engine._stats['budget_deferred_requests'] == 1
    assert deferred.value == before + 1
    assert _admit_all(engine) == []
    assert engine._stats['budget_deferrals'] == 2
    assert engine._stats['budget_deferred_requests'] == 1
    engine.shutdown()


@time_limit(120)
def test_in_flight_tokens_and_staggered_ends_in_the_pipelined_loop():
    """A running row with tokens in flight is where the device has it
    (``num_tokens + unacked``) with the steps it has left to dispatch, and
    its blocks come back a dispatch after its last window."""
    engine = _engine(num_blocks=21)  # 20 usable blocks
    short, long_ = _prompts((20, 20))
    rid = engine.add_request(short, SamplingParams(max_tokens=10))
    assert _admit_all(engine) == [rid]
    request = engine._requests[rid]
    # As after its prefill and one window of 4 in flight.
    request.output_ids.append(1)
    engine.sched.append_token(rid)
    engine.sched.prepare_decode(1, [rid], [4])
    engine._unacked[rid] = 4
    row = engine._budget_row(request)
    assert (row.length, row.steps, row.held) == (25, 5, 7)
    # The newcomer: 20 + its prefill's token, 23 steps to 44 tokens. The
    # short row ends at 30 tokens (8 blocks) after two more windows, when
    # the newcomer has 29 of its 44 (8 blocks): 9 more than the 7 held
    # now, of 13 spare. At its end the newcomer holds 11, with the short
    # row's blocks back: it fits ...
    engine.add_request(long_, SamplingParams(max_tokens=24))
    head = engine._requests[engine.sched.waiting_head()]
    assert engine._budget_row(head)[:3] == (21, 23, 0)
    assert engine._decode_budget_admits()
    # ... and under the pipelined loop too, where the short row's blocks
    # are held one dispatch longer (the newcomer at 33 tokens, 9 blocks).
    engine._windows_behind = 1
    assert engine._decode_budget_admits()
    # Four blocks fewer (9 spare) and that dispatch no longer fits;
    # without the window behind, the 9 of the short row's end still do.
    engine.sched._inner._free = engine.sched._inner._free[4:]
    assert not engine._decode_budget_admits()
    engine._windows_behind = 0
    assert engine._decode_budget_admits()
    engine.shutdown()


@time_limit(120)
def test_borrowed_prefixes_and_evictable_blocks_are_capacity():
    engine = _engine(num_blocks=21, enable_prefix_cache=True)
    params = SamplingParams(temperature=0.0, max_tokens=8)
    shared = _prompts((32,))[0]
    engine.generate_ids([shared], params)
    # Its 8 full prompt blocks stay cached and evictable; 12 are free.
    assert engine.prefix_cache.num_evictable == 8
    assert engine.sched.num_free_blocks == 12
    # Each ends at 40 tokens, 10 blocks: 20 in all, which only fits if
    # the evictable blocks count as capacity.
    for prompt in _prompts((32, 32), seed=1):
        engine.add_request(prompt, params)
    assert len(_admit_all(engine)) == 2
    assert not engine._stats['budget_deferrals']
    engine.shutdown()

    # Two requests behind the same cached prefix: the blocks they share
    # are held once, and the one that finishes first keeps them pinned.
    engine = _engine(num_blocks=21, enable_prefix_cache=True)
    engine.generate_ids([shared], params)
    first, second = (shared + tail for tail in _prompts((3, 3), seed=2))
    a = engine.add_request(first, SamplingParams(max_tokens=4))
    b = engine.add_request(second, SamplingParams(max_tokens=12))
    assert _admit_all(engine) == [a, b]
    for rid in (a, b):
        row = engine._budget_row(engine._requests[rid])
        assert (row.held, row.kept) == (9, 8)
    engine.shutdown()


# ----------------------------------------------------------- end to end
@time_limit(300)
def test_overfilled_pool_is_served_without_a_preemption():
    """The shape of ``mistral7b.batch_generate`` in small: one call whose
    prompts overfill the pool several times, all waiting at once, every
    request running to its budget."""
    lengths = (10, 20, 30, 12, 25, 18, 11, 22, 28, 14, 9, 17, 26, 13, 21, 16)
    prompts = _prompts(lengths)
    params = SamplingParams(temperature=0.0, max_tokens=12)
    settings = dict(
        enable_prefix_cache=True, prefill_chunk_tokens=16, decode_steps=8,
    )
    small = _engine(num_blocks=24, **settings)
    prompt_tokens = sum(lengths)
    assert prompt_tokens > 3 * 23 * 4  # the pool, several times over
    before = small.flight.total_recorded
    outputs = small.generate_ids(prompts, params)
    records = _since(small, before)
    assert not [r for r in records if r['kind'] == 'preempt']
    # Invariant C with nothing lost: every prompt token prefilled once.
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert sum(r['tokens'] for r in prefills) == prompt_tokens
    assert {r['route'] for r in prefills} == {'dense', 'chunk'}
    requests = [r for r in records if r['kind'] == 'request']
    assert len(requests) == len(prompts)
    for r in requests:
        assert r['output_tokens'] == 12 and r['preemptions'] == 0
        assert r['prefill_tokens'] == r['prompt_tokens']
    # The look-ahead is what held the others back, and it says so.
    assert small._stats['budget_deferred_requests'] >= 3
    assert small._stats['budget_deferrals'] >= 3
    assert small._ewma['budget_use'] == 1.0
    assert small.sched.num_free_blocks + small.prefix_cache.num_evictable == 23
    small.shutdown()

    # The same call against a pool that admits everything at once.
    large = _engine(num_blocks=400, **settings)
    assert large.generate_ids(prompts, params) == outputs
    assert not large._stats['budget_deferrals']
    large.shutdown()


@time_limit(180)
def test_a_head_that_waits_on_a_finish_in_flight_joins_the_next_window():
    """The pipelined loop learns of a finish one window late. When the
    look-ahead makes the head wait for blocks of a row whose last tokens
    are in flight, the loop fetches that window before it dispatches the
    next: no window carries only the rows that outlive the short one."""
    prompts = _prompts((20, 20, 20), seed=5)
    budgets = (21, 9, 13)  # after the prefill's token: 5, 2 and 3 windows

    def run(num_blocks):
        engine = _engine(num_blocks=num_blocks)
        before = engine.flight.total_recorded
        rids = [
            engine.add_request(
                prompt, SamplingParams(temperature=0.0, max_tokens=n)
            )
            for prompt, n in zip(prompts, budgets)
        ]
        engine._run_to_completion()
        records = _since(engine, before)
        assert not [r for r in records if r['kind'] == 'preempt']
        rows = [r['batch'] for r in records if r['kind'] == 'decode']
        outputs = [engine._finished.pop(rid).output_ids for rid in rids]
        deferred = engine._stats['budget_deferred_requests']
        engine.shutdown()
        return rows, outputs, deferred

    # 20 usable blocks: the third request (9 blocks at its end) fits
    # beside the long one (11) only when the short one's 8 are back.
    rows, outputs, deferred = run(21)
    assert deferred == 1
    # The short row's second window is fetched before the third is
    # dispatched, so the newcomer rides it: not [2, 2, 1, 2, 2, 1].
    assert rows == [2, 2, 2, 2, 2]
    assert [len(o) for o in outputs] == list(budgets)
    everything_at_once = run(400)
    assert everything_at_once[0] == [3, 3, 2, 1, 1]
    assert everything_at_once[1] == outputs


@pytest.fixture
def injector():
    faults = get_fault_injector()
    faults.disarm()
    yield faults
    faults.disarm()


@time_limit(300)
def test_loose_budgets_follow_what_answers_use_and_preemption_is_the_net(
    injector,
):
    """``max_tokens`` 2000 and stop tokens that end answers early: the
    worst case would run one row where the old rule ran four. After the
    first answer the walk follows the share of a budget that answers use.
    A pool-short event (forced through the ``sched_exhausted`` site) is
    recovered from and raises that share."""
    lengths = (20, 24, 28, 22, 26, 21, 25, 23)
    prompts = _prompts(lengths, seed=3)
    free_run = _engine(num_blocks=400, max_model_len=128)
    plain = free_run.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=16)
    )
    free_run.shutdown()
    # Every answer stops at its 16th token, or where that token first
    # shows up.
    want = [out[: out.index(out[15]) + 1] for out in plain]

    # 59 usable blocks: to max_model_len a row takes 32, so the worst
    # case admits one row; the prompts (6-8 blocks each) fit four at a
    # time.
    engine = _engine(
        num_blocks=60, max_model_len=128, max_dispatch_retries=3,
        retry_backoff_s=0.0,
    )
    rids = [
        engine.add_request(prompt, SamplingParams(
            temperature=0.0, max_tokens=2000, stop_token_ids=[out[15]]
        ))
        for prompt, out in zip(prompts, plain)
    ]
    engine.step()
    assert engine.sched.num_running == 1  # held back by the worst case
    assert engine._stats['budget_deferrals'] >= 1
    while not engine._finished:
        engine.step()
    use = engine._ewma['budget_use']
    assert use <= 16 / (128 - 28)  # an answer's share of its budget
    engine._admit()
    # Not below what the old rule admits: every slot is taken, or the
    # queue is empty, or the head's prompt itself does not fit.
    head = engine.sched.waiting_head()
    assert (
        head is None
        or engine.sched.num_running == 4
        or engine.kv.blocks_needed(engine._requests[head].num_tokens + 1)
        - len(engine.sched.block_row(head))
        > engine.sched.num_free_blocks
    )
    assert engine.sched.num_running > 1

    # The net: a pool-short event under rows the walk admitted, once
    # nothing waits (so that the step's admission changes no estimate).
    while engine.sched.num_waiting:
        engine.step()
    assert engine.sched.num_running
    use = engine._ewma['budget_use']
    injector.arm('sched_exhausted', times=1)
    engine.step()
    assert engine._stats['window_retries'] == 1
    assert engine._ewma['budget_use'] == min(1.0, 2 * use)
    while engine.has_unfinished:
        engine.step()
    got = [engine._finished.pop(rid) for rid in rids]
    assert [r.output_ids for r in got] == want
    assert all(r.finish_reason == 'stop' for r in got)
    assert engine.sched.num_free_blocks == 59
    engine.shutdown()
