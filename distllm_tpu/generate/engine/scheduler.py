"""Continuous-batching scheduler: native C++ core with a Python twin.

The policy layer of the generation engine (the analogue of vLLM's scheduler,
SURVEY.md §2.4 N1) extracted behind one interface:

- :class:`NativeScheduler` — ctypes binding over
  ``distllm_tpu/native/scheduler.cpp``; owns the block free-list, slot
  table, waiting queue, and preemption policy in C++.
- :class:`PyScheduler` — pure-Python implementation of the identical
  policy (fallback when no compiler is available; also the differential-
  test oracle).

Policy contract (both implementations, tested in lockstep):

- ``admit_next`` pops the waiting-queue head into the lowest free slot when
  blocks for ``num_tokens + 1`` are available (all-or-nothing). Blocks a
  request already carries (a borrowed prefix-cache prefix) count toward
  that budget: only the shortfall is allocated.
- ``prepare_decode(k, rids=None, ks=None)`` guarantees every running
  sequence can take ``k`` more tokens (k > 1 backs multi-step fused
  decode windows), preempting the youngest (highest rid) on OOM —
  recompute preemption: blocks freed, request to the FRONT of the
  waiting queue. ``rids`` restricts the guarantee to the listed rows
  (mixed serving windows: rows whose prefill chunks ride the window get
  no speculative decode headroom — their blocks were fully allocated at
  admission). ``ks`` (parallel to ``rids``) grants PER-ROW headroom
  instead of the uniform ``k`` — speculative verify windows reserve each
  row's own ``1 + draft`` span rather than the batch max
  (docs/speculative.md).
- ``trim(rid)`` returns owned tail blocks beyond what ``num_tokens + 1``
  needs to the free list (newest first, so a later extension re-pops the
  identical blocks) — how a speculative window's rejected-suffix
  reservation is rolled back to the never-drafted state.
- Block 0 is the reserved trash block and is never allocated.
- ``waiting_head`` names the request ``admit_next`` would try, without
  moving it (``None`` on an empty queue).

Admission by decode budget (the engine's gate in front of ``admit_next``,
``LLMEngine._admit_next_evicting``). The test above grants a prompt and
one token; a request whose ``max_tokens`` the pool cannot carry would be
admitted, outgrow the pool at the next ``prepare_decode`` and be
preempted: with 96 prompts waiting on a 10,240-token pool that was 90
preemptions a call. So before the head is admitted next to running rows
the engine asks :func:`decode_budget_fits`: if every running row and the
head decode to the end of their budgets, does the pool ever run short?
The scheduler knows no budget; the engine, which does, describes each
row as a :class:`BudgetRow` and passes free plus evictable blocks as
``spare``. The walk visits the rows in the order they finish. At each
finish the rows still alive hold the reservation the engine makes for
them by then (``LLMEngine._window_reserve``: what is in flight plus the
window's steps, capped by the row's own budget, row by row), and a
finished row's blocks are capacity again: its owned tail free, its
borrowed prefix evictable unless another request also holds it. No
watermark and no safety factor: with tight budgets the walk
refuses only an admission that ends in a preemption. With nothing
running the head is always tried, so :class:`SchedulerExhausted` keeps
its meaning, and recompute preemption stays as the net under what the
walk cannot see (per-row speculative headroom, an injected fault, a
budget estimate that was low). The pipelined loop learns of a finish
when it fetches the row's last window, a dispatch late (``behind``); when
the walk makes the head wait while such a window is in flight, the loop
fetches it before it dispatches the next
(``LLMEngine._head_waits_on_inflight``), so the head joins the next
window and none carries only the rows that outlive a wave.

Two kinds of state (a hybrid model, ``kv_cache.StatePool``). A sequence of
a model with recurrent layers holds blocks of its PAGED layers' pool and one
slot of the state pool. The slot is this scheduler's slot: ``admit_next``
takes the lowest free one, ``finish`` and preemption free it, so the
look-ahead's budget for a sequence is blocks of the paged layers plus one
slot, and "no free slot" is the deferral it already had. Where only one
layer in ten has pages (4 KiB a token), the pool rarely bounds the batch;
``max_num_seqs`` and the state bytes behind each slot do.

A second pool (a model with a WINDOWED cache group, ``kv_cache.WindowBlocks``,
docs/serving.md "Cache groups"). This scheduler owns the blocks of the
model's first, full-context group and knows no other. A windowed group's
blocks are the engine's to cover and free, dispatch by dispatch; what a row
holds of them is a constant, so the engine sizes that pool for every slot
at its constant (``LLMEngine._build_window_group``) and admission asks
nothing of it. ``finish`` and preemption here are followed by a release
there.

Borrowed prefixes (automatic prefix caching, docs/prefix_caching.md): a
request's block row may start with blocks OWNED BY THE PREFIX CACHE —
attached at ``add`` (cache hit) or marked afterwards with ``lend_prefix``
(this request's freshly prefilled prompt blocks entering the cache, OR
blocks mid-promotion from the host/disk KV tier — the engine lends them
the moment the promotion scatter is dispatched, so promotion-pending rows
behave exactly like borrowed prefixes in BOTH front-ends: counted toward
budgets, never freed to the free list mid-promotion, surviving
preemption). The scheduler never returns borrowed blocks to its free
list: ``finish`` and preemption free only the owned tail, and the cache
hands evicted blocks back through ``release_blocks``. Refcounts/eviction
policy live in ``kv_cache.PrefixCache``; the tier pools live in
``kv_cache.HostKVTier``/``DiskKVTier``; the scheduler only knows "the
first N blocks of this row are not mine to free".
"""

from __future__ import annotations

import ctypes
from collections import deque
from dataclasses import dataclass, field
from typing import NamedTuple, Protocol


class SchedulerExhausted(RuntimeError):
    """The block pool cannot serve even a lone request; raise to the caller.

    ``preempted`` carries rids already moved to the waiting queue by the
    same (failed) ``prepare_decode`` call — the engine must mark those
    requests WAITING before propagating, or its state diverges from the
    scheduler's.
    """

    def __init__(self, message: str, preempted: list[int] | None = None):
        super().__init__(message)
        self.preempted = list(preempted or [])


class BudgetRow(NamedTuple):
    """One row of the decode-budget walk, as the engine sees it now."""

    # Tokens the row has once every window already dispatched has landed
    # (``num_tokens + unacked``); a prefill still to run counts its token.
    length: int
    # Decode steps still to dispatch before the row's (expected) end.
    steps: int
    # Blocks in the row now.
    held: int
    # Blocks of the row that stay out of reach when it finishes: borrowed
    # prefix blocks that another request references too.
    kept: int


def decode_budget_fits(
    rows: 'list[BudgetRow]', spare: int, block_size: int, k: int,
    behind: int = 0,
) -> bool:
    """Can ``rows`` all decode to their ends within ``spare`` more blocks?

    ``k`` is the steps of a window, ``behind`` the windows in flight
    behind a dispatch (``pipeline_depth - 1`` in the pipelined loop, 0
    under ``step()``). Dispatch ``j`` (0 = the next one) has reserved a
    live row ``min(length + (j + 1) * k, length + steps)`` tokens: the
    engine reserves each row its own window, capped by its own end. A
    row's last window is dispatch ``ceil(steps / k) - 1``, and its blocks
    come back when that window is processed, ``behind`` dispatches later.
    Demand only grows between two finishes, so it is checked at the last
    dispatch each row is alive at.
    """

    def need(row: BudgetRow, j: int) -> int:
        tokens = row.length + min((j + 1) * k, row.steps)
        return max(row.held, -(-tokens // block_size))

    # Every row at its end at once (a prefill that ends its request holds
    # what admission grants, which is ``length``): the common case.
    if sum(need(row, row.steps) - row.held for row in rows) <= spare:
        return True
    last = [-(-row.steps // k) - 1 + behind for row in rows]
    order = sorted(range(len(rows)), key=last.__getitem__)
    returned = 0
    alive = 0
    for j in sorted({j for j in last if j >= 0}):
        while last[order[alive]] < j:
            gone = rows[order[alive]]
            # It took ``peak - held`` of the spare blocks and gives
            # ``peak - kept`` back.
            returned += gone.held - gone.kept
            alive += 1
        grown = sum(need(rows[i], j) - rows[i].held for i in order[alive:])
        if grown - returned > spare:
            return False
    return True


class Scheduler(Protocol):
    def add(
        self, rid: int, num_tokens: int, cached_blocks: 'list[int] | tuple' = ()
    ) -> None: ...

    def admit_next(self) -> int | None: ...

    def waiting_head(self) -> int | None: ...

    def prepare_decode(
        self,
        k: int = 1,
        rids: 'list[int] | None' = None,
        ks: 'list[int] | None' = None,
    ) -> list[int]: ...

    def append_token(self, rid: int) -> None: ...

    def trim(self, rid: int) -> int: ...

    def finish(self, rid: int) -> None: ...

    def lend_prefix(self, rid: int, num_blocks: int) -> None: ...

    def release_blocks(self, blocks: list[int]) -> None: ...

    def num_borrowed(self, rid: int) -> int: ...

    def slot(self, rid: int) -> int: ...

    def running(self) -> list[tuple[int, int]]: ...

    def block_row(self, rid: int) -> list[int]: ...

    @property
    def num_free_blocks(self) -> int: ...

    @property
    def num_running(self) -> int: ...

    @property
    def num_waiting(self) -> int: ...

    @property
    def has_unfinished(self) -> bool: ...


@dataclass
class _PyRequest:
    rid: int
    num_tokens: int
    blocks: list[int] = field(default_factory=list)
    slot: int = -1
    # First `num_borrowed` blocks are prefix-cache property: never freed
    # to the scheduler free list, and they survive recompute preemption.
    num_borrowed: int = 0


class PyScheduler:
    """Pure-Python scheduler (same observable policy as the C++ core)."""

    def __init__(self, num_blocks: int, block_size: int, max_num_seqs: int) -> None:
        if num_blocks < 2:
            raise ValueError('need >= 2 blocks (block 0 is reserved)')
        self._block_size = block_size
        self._free = list(range(num_blocks - 1, 0, -1))
        self._waiting: deque[int] = deque()
        self._slots: list[int] = [-1] * max_num_seqs
        self._requests: dict[int, _PyRequest] = {}

    def _blocks_needed(self, tokens: int) -> int:
        return (tokens + self._block_size - 1) // self._block_size

    def add(
        self, rid: int, num_tokens: int, cached_blocks: 'list[int] | tuple' = ()
    ) -> None:
        if rid in self._requests:
            raise ValueError(f'duplicate request id {rid}')
        self._requests[rid] = _PyRequest(
            rid,
            num_tokens,
            blocks=list(cached_blocks),
            num_borrowed=len(cached_blocks),
        )
        self._waiting.append(rid)

    def admit_next(self) -> int | None:
        if not self._waiting:
            return None
        try:
            slot = self._slots.index(-1)
        # distlint: disable=swallowed-exception -- no-free-slot is a normal admission outcome (None = defer), not a degradation; the wrapper counts deferrals
        except ValueError:
            return None
        rid = self._waiting[0]
        req = self._requests[rid]
        # Borrowed (and preemption-surviving) blocks already cover part of
        # the budget; only the shortfall comes out of the free list.
        short = self._blocks_needed(req.num_tokens + 1) - len(req.blocks)
        if short > len(self._free):
            if self.num_running == 0:
                raise SchedulerExhausted(
                    f'request {rid} needs {short} KV blocks but only '
                    f'{len(self._free)} are free with nothing running; '
                    'increase num_blocks'
                )
            return None
        self._waiting.popleft()
        req.blocks.extend(self._free.pop() for _ in range(short))
        req.slot = slot
        self._slots[slot] = rid
        return rid

    def waiting_head(self) -> int | None:
        return self._waiting[0] if self._waiting else None

    def _free_owned(self, req: _PyRequest) -> None:
        self._free.extend(req.blocks[req.num_borrowed :])
        del req.blocks[req.num_borrowed :]

    def _preempt_youngest(self) -> int | None:
        running = [r for r in self._slots if r >= 0]
        if len(running) <= 1:
            return None
        victim = self._requests[max(running)]
        self._free_owned(victim)
        self._slots[victim.slot] = -1
        victim.slot = -1
        self._waiting.appendleft(victim.rid)
        return victim.rid

    def _extend(self, req: _PyRequest, tokens: int) -> bool:
        while len(req.blocks) < self._blocks_needed(tokens):
            if not self._free:
                return False
            req.blocks.append(self._free.pop())
        return True

    def prepare_decode(
        self,
        k: int = 1,
        rids: 'list[int] | None' = None,
        ks: 'list[int] | None' = None,
    ) -> list[int]:
        """``rids`` (mixed serving windows) restricts the k-token capacity
        guarantee to the listed running requests: rows mid-prefill inside
        a mixed window already own blocks for their full prompt from
        admission, so extending them too would waste pool and provoke
        spurious preemptions. Victims are still chosen youngest-first over
        ALL running rows. ``None`` = every running row (classic policy).
        ``ks`` (parallel to ``rids``) overrides ``k`` per row — the
        speculative verify window's per-row ``1 + draft`` headroom."""
        if k < 1:
            raise ValueError('k must be >= 1')
        if ks is not None:
            if rids is None or len(ks) != len(rids):
                raise ValueError('ks must parallel rids')
            if any(kk < 1 for kk in ks):
                raise ValueError('per-row k must be >= 1')
            if len(set(rids)) != len(rids):
                # A duplicate rid would make the per-row k ambiguous (and
                # the Python/native twins would resolve it differently):
                # reject instead of silently picking one.
                raise ValueError('duplicate rids with per-row ks')
        per_row = dict(zip(rids, ks)) if ks is not None else None
        selected = None if rids is None else set(rids)
        preempted: list[int] = []
        for rid in list(self._slots):
            if rid < 0:
                continue
            if selected is not None and rid not in selected:
                continue  # not selected for decode this window
            req = self._requests[rid]
            if req.slot < 0:
                continue  # preempted earlier in this loop
            k_row = per_row[rid] if per_row is not None else k
            while not self._extend(req, req.num_tokens + k_row):
                victim = self._preempt_youngest()
                if victim is None:
                    raise SchedulerExhausted(
                        'KV cache exhausted with a single running sequence; '
                        'increase num_blocks or reduce max_model_len',
                        preempted=preempted,
                    )
                preempted.append(victim)
                if victim == rid:
                    break
        return preempted

    def append_token(self, rid: int) -> None:
        self._requests[rid].num_tokens += 1

    def trim(self, rid: int) -> int:
        """Free owned tail blocks beyond ``blocks_needed(num_tokens + 1)``.

        The rejected-suffix rollback of speculative windows: headroom
        reserved for drafts that did not survive verification returns to
        the free list, newest block first, so the free list (a LIFO) is
        restored to exactly its pre-reservation state and a later
        extension re-pops the identical blocks. Borrowed prefix blocks
        are never touched. Returns the number of blocks freed.
        """
        req = self._requests[rid]
        keep = max(self._blocks_needed(req.num_tokens + 1), req.num_borrowed)
        freed = len(req.blocks) - keep
        if freed <= 0:
            return 0
        self._free.extend(reversed(req.blocks[keep:]))
        del req.blocks[keep:]
        return freed

    def finish(self, rid: int) -> None:
        req = self._requests.pop(rid)
        # Borrowed prefix blocks belong to the prefix cache; only the
        # owned tail returns to the free list.
        self._free.extend(req.blocks[req.num_borrowed :])
        if req.slot >= 0:
            self._slots[req.slot] = -1
        try:
            self._waiting.remove(rid)
        # distlint: disable=swallowed-exception -- membership-probe control flow: finishing a RUNNING request is the common case and it is simply not in the waiting deque
        except ValueError:
            pass

    def lend_prefix(self, rid: int, num_blocks: int) -> None:
        """Extend ``rid``'s borrowed prefix to ``num_blocks`` blocks total
        (the prefix cache adopted this request's freshly prefilled prompt
        blocks). Idempotent for smaller values; never exceeds the row."""
        req = self._requests[rid]
        if num_blocks > len(req.blocks):
            raise ValueError(
                f'cannot lend {num_blocks} blocks of a {len(req.blocks)}-row'
            )
        req.num_borrowed = max(req.num_borrowed, num_blocks)

    def release_blocks(self, blocks: list[int]) -> None:
        """Return cache-evicted blocks to the free list."""
        self._free.extend(blocks)

    def num_borrowed(self, rid: int) -> int:
        return self._requests[rid].num_borrowed

    def slot(self, rid: int) -> int:
        return self._requests[rid].slot

    def running(self) -> list[tuple[int, int]]:
        """Occupied ``(slot, rid)`` pairs in slot order — O(max_num_seqs)."""
        return [(i, rid) for i, rid in enumerate(self._slots) if rid >= 0]

    def block_row(self, rid: int) -> list[int]:
        return list(self._requests[rid].blocks)

    @property
    def num_free_blocks(self) -> int:
        return len(self._free)

    @property
    def num_running(self) -> int:
        return sum(1 for r in self._slots if r >= 0)

    @property
    def num_waiting(self) -> int:
        return len(self._waiting)

    @property
    def has_unfinished(self) -> bool:
        return bool(self._waiting) or self.num_running > 0


class NativeScheduler:
    """ctypes binding over the C++ scheduler core."""

    def __init__(self, num_blocks: int, block_size: int, max_num_seqs: int) -> None:
        from distllm_tpu.native import build_library

        so_path = build_library('scheduler.cpp')
        if so_path is None:
            raise RuntimeError('native scheduler unavailable')
        lib = ctypes.CDLL(str(so_path))
        lib.sched_create.restype = ctypes.c_void_p
        lib.sched_create.argtypes = [ctypes.c_int32] * 3
        lib.sched_destroy.argtypes = [ctypes.c_void_p]
        lib.sched_add.restype = ctypes.c_int32
        lib.sched_add.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32]
        lib.sched_add_cached.restype = ctypes.c_int32
        lib.sched_add_cached.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        lib.sched_lend_prefix.restype = ctypes.c_int32
        lib.sched_lend_prefix.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int32,
        ]
        lib.sched_release_blocks.restype = ctypes.c_int32
        lib.sched_release_blocks.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        lib.sched_num_borrowed.restype = ctypes.c_int32
        lib.sched_num_borrowed.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sched_admit_next.restype = ctypes.c_int64
        lib.sched_admit_next.argtypes = [ctypes.c_void_p]
        lib.sched_waiting_head.restype = ctypes.c_int64
        lib.sched_waiting_head.argtypes = [ctypes.c_void_p]
        lib.sched_prepare_decode_k.restype = ctypes.c_int32
        lib.sched_prepare_decode_k.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.sched_prepare_decode_rows.restype = ctypes.c_int32
        lib.sched_prepare_decode_rows.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.sched_prepare_decode_rows_k.restype = ctypes.c_int32
        lib.sched_prepare_decode_rows_k.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.sched_trim.restype = ctypes.c_int32
        lib.sched_trim.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        for name in ('sched_append_token', 'sched_finish'):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int32
            fn.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sched_slot.restype = ctypes.c_int32
        lib.sched_slot.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sched_running.restype = ctypes.c_int32
        lib.sched_running.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.sched_block_row.restype = ctypes.c_int32
        lib.sched_block_row.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        for name in (
            'sched_num_free',
            'sched_num_running',
            'sched_num_waiting',
            'sched_has_unfinished',
        ):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int32
            fn.argtypes = [ctypes.c_void_p]

        handle = lib.sched_create(num_blocks, block_size, max_num_seqs)
        if not handle:
            raise RuntimeError(
                f'sched_create({num_blocks}, {block_size}, {max_num_seqs}) failed'
            )
        self._lib = lib
        self._handle = handle
        self._max_num_seqs = max_num_seqs
        self._num_blocks = num_blocks

    def add(
        self, rid: int, num_tokens: int, cached_blocks: 'list[int] | tuple' = ()
    ) -> None:
        if cached_blocks:
            arr = (ctypes.c_int32 * len(cached_blocks))(*cached_blocks)
            rc = self._lib.sched_add_cached(
                self._handle, rid, num_tokens, arr, len(cached_blocks)
            )
        else:
            rc = self._lib.sched_add(self._handle, rid, num_tokens)
        if rc == -2:
            raise ValueError(f'duplicate request id {rid}')
        if rc != 0:
            raise RuntimeError(f'sched_add failed: {rc}')

    def admit_next(self) -> int | None:
        rid = int(self._lib.sched_admit_next(self._handle))
        if rid == -2:
            raise SchedulerExhausted(
                'request needs more KV blocks than are free with nothing '
                'running; increase num_blocks'
            )
        return None if rid < 0 else rid

    def waiting_head(self) -> int | None:
        rid = int(self._lib.sched_waiting_head(self._handle))
        return None if rid < 0 else rid

    def prepare_decode(
        self,
        k: int = 1,
        rids: 'list[int] | None' = None,
        ks: 'list[int] | None' = None,
    ) -> list[int]:
        if k < 1:
            raise ValueError('k must be >= 1')
        if ks is not None:
            if rids is None or len(ks) != len(rids):
                raise ValueError('ks must parallel rids')
            if any(kk < 1 for kk in ks):
                raise ValueError('per-row k must be >= 1')
            if len(set(rids)) != len(rids):
                raise ValueError('duplicate rids with per-row ks')
        out = (ctypes.c_int64 * self._max_num_seqs)()
        if rids is None:
            n = int(self._lib.sched_prepare_decode_k(self._handle, k, out))
        else:
            arr = (ctypes.c_int64 * max(1, len(rids)))(*rids)
            ks_arr = (
                (ctypes.c_int32 * max(1, len(ks)))(*ks)
                if ks is not None
                else None
            )
            n = int(
                self._lib.sched_prepare_decode_rows_k(
                    self._handle, k, arr, ks_arr, len(rids), out
                )
            )
        if n < 0:
            # Fatal encoding is -(1 + n_preempted): preemptions already
            # performed are not rolled back and must reach the engine.
            raise SchedulerExhausted(
                'KV cache exhausted with a single running sequence; '
                'increase num_blocks or reduce max_model_len',
                preempted=[int(out[i]) for i in range(-n - 1)],
            )
        return [int(out[i]) for i in range(n)]

    def append_token(self, rid: int) -> None:
        if self._lib.sched_append_token(self._handle, rid) != 0:
            raise KeyError(rid)

    def trim(self, rid: int) -> int:
        n = int(self._lib.sched_trim(self._handle, rid))
        if n < 0:
            raise KeyError(rid)
        return n

    def finish(self, rid: int) -> None:
        if self._lib.sched_finish(self._handle, rid) != 0:
            raise KeyError(rid)

    def lend_prefix(self, rid: int, num_blocks: int) -> None:
        rc = self._lib.sched_lend_prefix(self._handle, rid, num_blocks)
        if rc == -1:
            raise KeyError(rid)
        if rc != 0:
            raise ValueError(
                f'cannot lend {num_blocks} blocks of request {rid}\'s row'
            )

    def release_blocks(self, blocks: list[int]) -> None:
        if not blocks:
            return
        arr = (ctypes.c_int32 * len(blocks))(*blocks)
        if self._lib.sched_release_blocks(self._handle, arr, len(blocks)) != 0:
            raise RuntimeError('sched_release_blocks failed')

    def num_borrowed(self, rid: int) -> int:
        n = int(self._lib.sched_num_borrowed(self._handle, rid))
        if n < 0:
            raise KeyError(rid)
        return n

    def slot(self, rid: int) -> int:
        return int(self._lib.sched_slot(self._handle, rid))

    def running(self) -> list[tuple[int, int]]:
        slots = (ctypes.c_int32 * self._max_num_seqs)()
        rids = (ctypes.c_int64 * self._max_num_seqs)()
        n = int(self._lib.sched_running(self._handle, slots, rids))
        return [(int(slots[i]), int(rids[i])) for i in range(n)]

    def block_row(self, rid: int) -> list[int]:
        out = (ctypes.c_int32 * self._num_blocks)()
        n = int(self._lib.sched_block_row(self._handle, rid, out, self._num_blocks))
        if n < 0:
            raise KeyError(rid)
        return [int(out[i]) for i in range(n)]

    @property
    def num_free_blocks(self) -> int:
        return int(self._lib.sched_num_free(self._handle))

    @property
    def num_running(self) -> int:
        return int(self._lib.sched_num_running(self._handle))

    @property
    def num_waiting(self) -> int:
        return int(self._lib.sched_num_waiting(self._handle))

    @property
    def has_unfinished(self) -> bool:
        return bool(self._lib.sched_has_unfinished(self._handle))

    def __del__(self) -> None:  # pragma: no cover - interpreter teardown
        lib = getattr(self, '_lib', None)
        handle = getattr(self, '_handle', None)
        if lib is not None and handle:
            lib.sched_destroy(handle)
            self._handle = None


class InstrumentedScheduler:
    """Delegating wrapper that publishes scheduler state as metrics.

    Wraps either implementation (policy untouched — the differential tests
    drive the raw classes) and keeps the process-wide gauges/counters in
    ``observability.instruments`` current on every mutating call: queue
    depth, running slots, KV-block occupancy, admit/defer decisions, and
    preemptions. Gauges are process-wide; with several engines in one
    process the last mutator wins (serving runs one engine per process).
    """

    def __init__(self, inner: Scheduler, num_blocks: int) -> None:
        from distllm_tpu.observability import instruments
        from distllm_tpu.observability.flight import get_flight_recorder

        self._inner = inner
        self._m = instruments
        # Preemptions and pool exhaustion are the scheduler events worth a
        # flight-ring entry: rare, and exactly what a post-mortem needs.
        # Admission defers are counters only — they fire every loop under
        # load and would evict useful ring history.
        self._flight = get_flight_recorder()
        self._usable_blocks = num_blocks - 1  # block 0 is reserved
        self._m.KV_BLOCKS_TOTAL.set(self._usable_blocks)
        self._sync()

    def _sync(self) -> None:
        in_use = self._usable_blocks - self._inner.num_free_blocks
        self._m.KV_BLOCKS_IN_USE.set(in_use)
        self._m.KV_OCCUPANCY.set(
            in_use / self._usable_blocks if self._usable_blocks else 0.0
        )
        self._m.SCHED_QUEUE_DEPTH.set(self._inner.num_waiting)
        self._m.SCHED_RUNNING.set(self._inner.num_running)

    def add(
        self, rid: int, num_tokens: int, cached_blocks: 'list[int] | tuple' = ()
    ) -> None:
        self._inner.add(rid, num_tokens, cached_blocks)
        self._sync()

    def admit_next(self) -> int | None:
        rid = self._inner.admit_next()
        if rid is not None:
            self._m.SCHED_ADMITTED.inc()
            self._sync()
        elif self._inner.num_waiting:
            self._m.SCHED_DEFERRED.labels(reason='capacity').inc()
        return rid

    def waiting_head(self) -> int | None:
        return self._inner.waiting_head()

    def prepare_decode(
        self,
        k: int = 1,
        rids: 'list[int] | None' = None,
        ks: 'list[int] | None' = None,
    ) -> list[int]:
        try:
            preempted = self._inner.prepare_decode(k, rids, ks)
        except SchedulerExhausted as exc:
            # Preemptions performed before the fatal exhaustion still
            # happened; count them before propagating.
            if exc.preempted:
                self._m.SCHED_PREEMPTIONS.inc(len(exc.preempted))
            self._sync()
            self._flight.record(
                'event',
                event='scheduler_exhausted',
                error=str(exc)[:300],
                preempted=list(exc.preempted),
                free_blocks=self._inner.num_free_blocks,
                queue_depth=self._inner.num_waiting,
            )
            raise
        if preempted:
            # The 'preempt' flight record is the engine's to write
            # (LLMEngine._note_preempted): only it knows the tokens each
            # victim loses.
            self._m.SCHED_PREEMPTIONS.inc(len(preempted))
        self._sync()
        return preempted

    def append_token(self, rid: int) -> None:
        # No _sync: appending only bumps the token count — block
        # allocation happens in prepare_decode, which does sync.
        self._inner.append_token(rid)

    def trim(self, rid: int) -> int:
        freed = self._inner.trim(rid)
        if freed:
            self._sync()
        return freed

    def finish(self, rid: int) -> None:
        self._inner.finish(rid)
        self._sync()

    def lend_prefix(self, rid: int, num_blocks: int) -> None:
        # No _sync: lending only re-labels ownership — occupancy unchanged.
        self._inner.lend_prefix(rid, num_blocks)

    def release_blocks(self, blocks: list[int]) -> None:
        self._inner.release_blocks(blocks)
        self._sync()

    def num_borrowed(self, rid: int) -> int:
        return self._inner.num_borrowed(rid)

    def slot(self, rid: int) -> int:
        return self._inner.slot(rid)

    def running(self) -> list[tuple[int, int]]:
        return self._inner.running()

    def block_row(self, rid: int) -> list[int]:
        return self._inner.block_row(rid)

    @property
    def num_free_blocks(self) -> int:
        return self._inner.num_free_blocks

    @property
    def num_running(self) -> int:
        return self._inner.num_running

    @property
    def num_waiting(self) -> int:
        return self._inner.num_waiting

    @property
    def has_unfinished(self) -> bool:
        return self._inner.has_unfinished


def make_scheduler(
    num_blocks: int,
    block_size: int,
    max_num_seqs: int,
    prefer_native: bool = True,
) -> Scheduler:
    if prefer_native:
        try:
            return NativeScheduler(num_blocks, block_size, max_num_seqs)
        except (RuntimeError, OSError) as exc:
            # The Python twin is a tested drop-in, but WHICH scheduler
            # served must never be a silent guess in a perf investigation.
            from distllm_tpu.observability.instruments import log_event

            log_event(
                f'[engine] native scheduler unavailable ({exc!r:.120}); '
                'using the Python twin',
                component='engine',
            )
    return PyScheduler(num_blocks, block_size, max_num_seqs)
