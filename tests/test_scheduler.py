"""Native C++ scheduler vs the Python twin: identical decisions.

The continuous-batching policy (admission, block budget, recompute
preemption — the role vLLM's scheduler plays for the reference,
SURVEY.md §2.4 N1) ships as a C++ core with a Python oracle; these tests
drive both with the same workloads and require decision-for-decision
equality, then exercise the policy edges on either implementation.
"""

from __future__ import annotations

import numpy as np
import pytest

from distllm_tpu.generate.engine.scheduler import (
    NativeScheduler,
    PyScheduler,
    SchedulerExhausted,
    make_scheduler,
)


def native_available() -> bool:
    try:
        NativeScheduler(8, 4, 2)
        return True
    except (RuntimeError, OSError):
        return False


requires_native = pytest.mark.skipif(
    not native_available(), reason='no C++ toolchain'
)


def drive(sched, seed: int, steps: int = 200):
    """Random workload driver; returns the full decision trace."""
    rng = np.random.default_rng(seed)
    trace = []
    next_rid = 0
    live: set[int] = set()
    for _ in range(steps):
        action = rng.integers(0, 4)
        if action == 0 or not live:
            tokens = int(rng.integers(1, 40))
            sched.add(next_rid, tokens)
            live.add(next_rid)
            trace.append(('add', next_rid, tokens))
            next_rid += 1
        elif action == 1:
            admitted = []
            try:
                while (rid := sched.admit_next()) is not None:
                    admitted.append(rid)
            except SchedulerExhausted:
                admitted.append('EXHAUSTED')
            trace.append(('admit', tuple(admitted)))
        elif action == 2:
            if sched.num_running:
                # k > 1 exercises the multi-step window reservation path.
                k = int(rng.integers(1, 6))
                # Half the time restrict to a random running subset — the
                # mixed-serving-window path (rows mid-prefill get no
                # decode headroom); None = classic all-rows policy.
                rids = None
                ks = None
                if rng.integers(0, 2):
                    rids = [
                        rid for rid in sorted(live)
                        if sched.slot(rid) >= 0 and rng.integers(0, 2)
                    ]
                    if rng.integers(0, 2):
                        # Per-row headroom (speculative verify windows):
                        # each selected row gets its own k.
                        ks = [int(rng.integers(1, 6)) for _ in rids]
                try:
                    preempted = sched.prepare_decode(k, rids, ks)
                except SchedulerExhausted as exc:
                    # Fatal path reports prior same-call preemptions too;
                    # both implementations must agree on them.
                    preempted = ['EXHAUSTED', tuple(exc.preempted)]
                trace.append(
                    (
                        'prepare', k,
                        tuple(rids) if rids is not None else None,
                        tuple(ks) if ks is not None else None,
                        tuple(preempted),
                    )
                )
                for rid in list(live):
                    if sched.slot(rid) >= 0:
                        sched.append_token(rid)
                        trace.append(('token', rid))
                # Rejected-suffix rollback: trim a random running row's
                # over-reservation back to num_tokens + 1 coverage.
                running_now = [r for r in sorted(live) if sched.slot(r) >= 0]
                if running_now and rng.integers(0, 2):
                    victim = running_now[
                        int(rng.integers(0, len(running_now)))
                    ]
                    trace.append(('trim', victim, sched.trim(victim)))
        else:
            running = [rid for rid in live if sched.slot(rid) >= 0]
            if running:
                rid = running[int(rng.integers(0, len(running)))]
                sched.finish(rid)
                live.discard(rid)
                trace.append(('finish', rid))
        trace.append(
            (
                'state', sched.num_free_blocks, sched.num_running,
                sched.num_waiting, sched.waiting_head(),
            )
        )
    # Block rows of everything still live (allocation order must agree too).
    for rid in sorted(live):
        trace.append(('blocks', rid, tuple(sched.block_row(rid))))
    return trace


@requires_native
@pytest.mark.parametrize('seed', [0, 1, 2, 3, 4])
def test_native_matches_python_oracle(seed):
    py = PyScheduler(num_blocks=24, block_size=4, max_num_seqs=3)
    cc = NativeScheduler(num_blocks=24, block_size=4, max_num_seqs=3)
    assert drive(cc, seed) == drive(py, seed)


@requires_native
def test_make_scheduler_prefers_native():
    sched = make_scheduler(16, 4, 2, prefer_native=True)
    assert isinstance(sched, NativeScheduler)


@pytest.fixture(params=['py', 'native'])
def sched_factory(request):
    if request.param == 'native' and not native_available():
        pytest.skip('no C++ toolchain')
    cls = PyScheduler if request.param == 'py' else NativeScheduler

    def make(num_blocks=16, block_size=4, max_num_seqs=2):
        return cls(num_blocks, block_size, max_num_seqs)

    return make


class TestPolicy:
    def test_admission_assigns_lowest_slot_and_blocks(self, sched_factory):
        s = sched_factory()
        s.add(0, 5)  # needs ceil(6/4) = 2 blocks
        assert s.admit_next() == 0
        assert s.slot(0) == 0
        assert len(s.block_row(0)) == 2
        assert s.num_free_blocks == 15 - 2
        assert s.admit_next() is None

    def test_admission_blocked_until_slot_frees(self, sched_factory):
        s = sched_factory(max_num_seqs=1)
        s.add(0, 3)
        s.add(1, 3)
        assert s.admit_next() == 0
        assert s.admit_next() is None  # no slot
        s.finish(0)
        assert s.admit_next() == 1

    def test_preemption_frees_youngest_to_waiting_front(self, sched_factory):
        # 7 usable blocks, block_size 1: two sequences of 3 fit, then the
        # older one's growth preempts the younger.
        s = sched_factory(num_blocks=8, block_size=1, max_num_seqs=2)
        s.add(0, 3)
        s.add(1, 3)
        assert s.admit_next() == 0  # takes 4 blocks (3 tokens + 1 headroom)
        assert s.admit_next() is None  # rid 1 needs 4, only 3 free
        assert s.slot(1) == -1
        assert s.num_waiting == 1
        # grow rid 0 to fill the pool, then prepare_decode keeps it running
        for _ in range(3):
            s.append_token(0)
            assert s.prepare_decode() == []
        assert s.num_free_blocks == 0

    def test_preemption_round_trip(self, sched_factory):
        s = sched_factory(num_blocks=9, block_size=1, max_num_seqs=2)
        s.add(0, 3)
        s.add(1, 3)
        assert s.admit_next() == 0
        assert s.admit_next() == 1
        assert s.num_free_blocks == 0
        s.append_token(0)  # rid 0 now needs a 5th block
        preempted = s.prepare_decode()
        assert preempted == [1]
        assert s.slot(1) == -1
        assert s.num_waiting == 1
        assert s.block_row(1) == []
        # rid 1 re-admits once rid 0 finishes, with tokens intact
        s.finish(0)
        assert s.admit_next() == 1
        assert len(s.block_row(1)) == 4  # 3 tokens + 1 headroom

    def test_exhausted_single_sequence_raises(self, sched_factory):
        s = sched_factory(num_blocks=4, block_size=1, max_num_seqs=2)
        s.add(0, 2)
        assert s.admit_next() == 0  # takes all 3 usable blocks (2+1)
        s.append_token(0)
        with pytest.raises(SchedulerExhausted):
            s.prepare_decode()  # needs a 4th block, pool has 3 usable

    def test_exhausted_reports_prior_preemptions(self, sched_factory):
        # rid 0 grows so much in one prepare_decode that preempting BOTH
        # younger sequences still cannot satisfy it: the fatal error must
        # carry the preemptions already performed (they are not rolled
        # back — their requests sit in the waiting queue).
        s = sched_factory(num_blocks=10, block_size=1, max_num_seqs=3)
        for rid in (0, 1, 2):
            s.add(rid, 2)
            assert s.admit_next() == rid  # 3 blocks each: pool now empty
        for _ in range(7):
            s.append_token(0)  # rid 0 now needs blocks for 10 tokens
        with pytest.raises(SchedulerExhausted) as excinfo:
            s.prepare_decode()
        assert excinfo.value.preempted == [2, 1]
        assert s.slot(1) == -1 and s.slot(2) == -1
        assert s.num_waiting == 2

    def test_admit_impossible_request_raises(self, sched_factory):
        s = sched_factory(num_blocks=4, block_size=1, max_num_seqs=2)
        s.add(0, 10)
        with pytest.raises(SchedulerExhausted):
            s.admit_next()

    def test_duplicate_rid_rejected(self, sched_factory):
        s = sched_factory()
        s.add(0, 1)
        with pytest.raises(ValueError):
            s.add(0, 1)

    def test_finish_waiting_request(self, sched_factory):
        s = sched_factory()
        s.add(0, 1)
        s.finish(0)
        assert not s.has_unfinished


class TestPrepareDecodeK:
    """Multi-token reservation (the fused decode window's contract)."""

    @pytest.fixture(params=['py', 'native'])
    def sched_factory(self, request):
        if request.param == 'native' and not native_available():
            pytest.skip('no C++ toolchain')
        cls = PyScheduler if request.param == 'py' else NativeScheduler
        return cls

    def test_reserves_k_tokens_of_blocks(self, sched_factory):
        sched = sched_factory(num_blocks=32, block_size=4, max_num_seqs=2)
        sched.add(0, 6)  # needs 2 blocks for 7 tokens at admission
        assert sched.admit_next() == 0
        owned = len(sched.block_row(0))
        # Reserve 9 more tokens: 6 + 9 = 15 -> ceil(15/4) = 4 blocks.
        sched.prepare_decode(9)
        assert len(sched.block_row(0)) == 4
        assert len(sched.block_row(0)) >= owned

    def test_k_preempts_youngest_on_pressure(self, sched_factory):
        sched = sched_factory(num_blocks=8, block_size=4, max_num_seqs=2)
        sched.add(0, 4)
        sched.add(1, 4)
        assert sched.admit_next() == 0
        assert sched.admit_next() == 1
        # 7 usable blocks; both own 2 (4+1 tokens), 3 free. Reserving 12
        # more tokens each needs 2 extra blocks per sequence -> the second
        # extension falls short and the youngest (1) is preempted.
        preempted = sched.prepare_decode(12)
        assert preempted == [1]
        assert sched.slot(1) == -1
        assert len(sched.block_row(0)) == 4  # ceil((4+12)/4)

    def test_k_invalid_raises(self, sched_factory):
        sched = sched_factory(num_blocks=8, block_size=4, max_num_seqs=2)
        with pytest.raises(ValueError):
            sched.prepare_decode(0)

    def test_rows_filter_extends_only_selected(self, sched_factory):
        """Mixed serving windows: rows mid-prefill ride the window but
        take no decode steps, so prepare_decode(k, rids) must grant the
        k-token headroom only to the listed rows."""
        sched = sched_factory(num_blocks=16, block_size=4, max_num_seqs=3)
        sched.add(0, 4)
        sched.add(1, 4)
        assert sched.admit_next() == 0
        assert sched.admit_next() == 1
        free_before = sched.num_free_blocks
        assert sched.prepare_decode(8, [0]) == []
        assert len(sched.block_row(0)) == 3  # ceil((4+8)/4)
        assert len(sched.block_row(1)) == 2  # untouched
        assert sched.num_free_blocks == free_before - 1
        # Empty selection is a no-op (chunk-only windows never call this,
        # but the contract must hold).
        assert sched.prepare_decode(8, []) == []
        assert sched.num_free_blocks == free_before - 1

    def test_per_row_ks_extends_each_row_its_own_headroom(
        self, sched_factory
    ):
        """Speculative verify windows: prepare_decode(k, rids, ks) grants
        each listed row ITS OWN reservation instead of the batch max."""
        sched = sched_factory(num_blocks=32, block_size=4, max_num_seqs=3)
        sched.add(0, 4)
        sched.add(1, 4)
        assert sched.admit_next() == 0
        assert sched.admit_next() == 1
        assert sched.prepare_decode(1, [0, 1], [9, 1]) == []
        assert len(sched.block_row(0)) == 4  # ceil((4+9)/4)
        assert len(sched.block_row(1)) == 2  # ceil((4+1)/4) — untouched

    def test_per_row_ks_validation(self, sched_factory):
        sched = sched_factory(num_blocks=16, block_size=4, max_num_seqs=2)
        sched.add(0, 4)
        assert sched.admit_next() == 0
        with pytest.raises(ValueError):
            sched.prepare_decode(1, [0], [2, 3])  # length mismatch
        with pytest.raises(ValueError):
            sched.prepare_decode(1, [0], [0])  # per-row k < 1
        with pytest.raises(ValueError):
            sched.prepare_decode(1, None, [2])  # ks without rids
        with pytest.raises(ValueError):
            # duplicate rids make the per-row k ambiguous (and would
            # resolve differently in the two backends)
            sched.prepare_decode(1, [0, 0], [2, 3])

    def test_trim_returns_overreservation_restoring_free_order(
        self, sched_factory
    ):
        """trim frees owned tail blocks beyond num_tokens + 1, newest
        first, so the LIFO free list is restored exactly — a later
        extension re-pops the identical blocks (the never-drafted-state
        equality the speculative rollback relies on)."""
        sched = sched_factory(num_blocks=16, block_size=4, max_num_seqs=2)
        sched.add(0, 4)
        assert sched.admit_next() == 0
        free_before = sched.num_free_blocks
        row_before = sched.block_row(0)
        assert sched.prepare_decode(9, [0]) == []  # reserve to 4 blocks
        assert len(sched.block_row(0)) == 4
        assert sched.trim(0) == 2  # back to ceil(5/4) = 2 blocks
        assert sched.block_row(0) == row_before
        assert sched.num_free_blocks == free_before
        # Re-extending hands back the same blocks in the same order.
        grown = sched.block_row(0)
        sched.prepare_decode(9, [0])
        assert sched.block_row(0)[: len(grown)] == grown
        assert sched.trim(0) == 2
        assert sched.num_free_blocks == free_before

    def test_trim_noop_and_unknown_rid(self, sched_factory):
        sched = sched_factory(num_blocks=16, block_size=4, max_num_seqs=2)
        sched.add(0, 4)
        assert sched.admit_next() == 0
        assert sched.trim(0) == 0  # admission reserve is exactly right
        with pytest.raises(KeyError):
            sched.trim(99)

    def test_trim_never_frees_borrowed_prefix(self, sched_factory):
        """Borrowed (prefix-cache) blocks are cache property even when
        num_tokens shrinks below their coverage after preemption."""
        sched = sched_factory(num_blocks=16, block_size=4, max_num_seqs=2)
        sched.add(0, 3, cached_blocks=[5, 6, 7])  # 12 cached tokens > 3+1
        assert sched.admit_next() == 0
        assert sched.trim(0) == 0
        assert sched.block_row(0) == [5, 6, 7]

    def test_rows_filter_can_preempt_unselected_victim(self, sched_factory):
        """Victims are still chosen youngest-first over ALL running rows:
        a mid-prefill (unselected) youngest can be recompute-preempted to
        fund a decode-ready row's reservation."""
        sched = sched_factory(num_blocks=8, block_size=4, max_num_seqs=2)
        sched.add(0, 4)
        sched.add(1, 4)
        assert sched.admit_next() == 0
        assert sched.admit_next() == 1
        # 7 usable; each owns 2, 3 free. Row 0 reserving 20 more tokens
        # needs ceil(24/4)=6 blocks (+4): only preempting row 1 funds it.
        preempted = sched.prepare_decode(20, [0])
        assert preempted == [1]
        assert sched.slot(1) == -1
        assert len(sched.block_row(0)) == 6
