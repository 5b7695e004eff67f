"""Paged KV cache: HBM block pool, automatic prefix cache, KV tiers.

The TPU replacement for vLLM's paged KV memory management (SURVEY.md
section 2.4 N1): K/V live as ``[L, num_blocks, block_size, N_kv * Hd]``
device arrays, a token's heads folded into one row as the paged kernel reads
them (``ops/paged_attention``; a reshape of the two minor dims of a pool is a
copy of it on the TPU, so no serving program makes one); sequences own lists
of block ids. Block 0 is the reserved TRASH block — padded scatter writes
land there. A block OUTSIDE the pool (a tier's entry, a ``.kvblock`` payload,
what a host reader gets) has the logical ``[.., block_size, N_kv, Hd]``.

Who holds which block is the scheduler's account (``engine/scheduler.py``;
a windowed group's is :class:`WindowBlocks`): there is no free list here.

:class:`PrefixCache` is the automatic prefix cache (SGLang-style radix
reuse over full paged blocks; docs/prefix_caching.md): a token-block
hash-chain → block-id map with per-block request refcounts and LRU
eviction of unreferenced blocks. It owns the REUSE policy only — physical
block accounting stays with the scheduler, which marks cache-held blocks
as a request's "borrowed prefix" (``scheduler.py``).

:class:`HostKVTier`, :class:`DiskKVTier`, and :class:`PeerKVTier` extend
the cache past HBM (docs/prefix_caching.md "Tier hierarchy",
docs/routing.md "Peer KV tier"): eviction cascades
HBM → host-RAM → disk → drop instead of dropping KV at the first tier,
and the engine promotes tier hits back into the paged pool via async
``device_put`` overlapped with decode windows. Lookup falls through
host → disk → **peer**: a replica that misses locally can adopt a
sibling replica's spilled blocks over the zmq fabric
(``parallel/fabric.py``) exactly like a disk promotion. All tiers are
keyed by the same chained digests and exchange the same ``.kvblock`` v2
payload (:func:`encode_kvblock` / :func:`decode_kvblock`); the disk
tier's digest-named files persist warm prefixes across engine restarts.

:class:`StatePool` holds what a sequence of a hybrid model keeps beside
its pages: the recurrent layers' fixed state, one slot a running sequence
(the scheduler's slot), per-layer buffers the decode window rewrites in
place. :class:`PagedKVCache` is then built over the paged layers only.

:class:`WindowBlocks` is the free list and the per-sequence holdings of a
WINDOWED paged group's pool (a model whose ``cache_spec()`` declares one,
docs/serving.md "Cache groups"): a sequence holds only the blocks a query of
its next dispatch still sees, and gives back the rest.

Mixed serving windows (docs/serving.md) write prefill-chunk K/V inside
decode dispatches; those writes always land in blocks the owning request
was granted at admission (the full prompt is budgeted up front), so no
block here ever changes owner while a window is in flight — the engine's
drain-before-preempt guard plus ``prepare_decode(..., rids=...)`` keep
that invariant.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from collections import OrderedDict
from pathlib import Path
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def hash_block_tokens(
    parent: bytes | None, tokens: Sequence[int]
) -> bytes:
    """Digest of one full token block, chained through its prefix.

    The chain (``h_i = H(h_{i-1} || tokens_i)``) makes a block's digest
    identify the ENTIRE prefix up to and including it, so a flat
    digest → block map behaves like a radix trie: matching the longest
    cached prefix is "walk digests until one misses". sha256 rather than
    Python ``hash``: digests index physical KV blocks, and a collision
    would silently serve another prompt's KV.
    """
    h = hashlib.sha256(parent or b'')
    h.update(b','.join(str(int(t)).encode() for t in tokens))
    return h.digest()


def block_digests(
    prompt_ids: Sequence[int], block_size: int
) -> list[bytes]:
    """Chained digests for every FULL block of ``prompt_ids``.

    Partial trailing blocks are not hashable (their content is not yet
    final — later tokens land in them), so reuse granularity is whole
    blocks; the COW path in the engine covers the aligned full-cover case.
    """
    digests: list[bytes] = []
    parent: bytes | None = None
    for start in range(0, len(prompt_ids) - block_size + 1, block_size):
        parent = hash_block_tokens(
            parent, prompt_ids[start : start + block_size]
        )
        digests.append(parent)
    return digests


@dataclass
class _CacheEntry:
    block_id: int
    refcount: int = 0  # live requests referencing this block
    holders: set = field(default_factory=set)  # rids, for shared-block gauge


class PrefixCache:
    """Digest-chain → KV-block map with refcounts and LRU eviction.

    Ownership protocol (engine-driven; see docs/prefix_caching.md):

    - ``acquire(rid, digests)`` — longest-prefix match; increfs every
      matched block for ``rid`` and returns the block ids. Matched blocks
      leave the evictable LRU.
    - ``insert(rid, digest, block_id)`` — adopt a freshly prefilled prompt
      block (the engine then marks it borrowed in the scheduler via
      ``lend_prefix``). Returns False when the digest is already cached
      (first writer wins; the caller keeps its duplicate block private).
    - ``release(rid)`` — drop every reference ``rid`` holds; blocks whose
      refcount reaches zero become LRU-evictable but KEEP their KV
      contents (that persistence is the whole point).
    - ``evict(max_blocks)`` — pop least-recently-used evictable blocks and
      return their ids for the scheduler's free list.

    Purely host-side bookkeeping: never touches device arrays and never
    frees blocks itself.
    """

    def __init__(self, block_size: int) -> None:
        self.block_size = block_size
        self._entries: dict[bytes, _CacheEntry] = {}
        # digest -> block_id for refcount==0 entries, LRU order (oldest
        # first). Entries stay in _entries while evictable.
        self._evictable: 'OrderedDict[bytes, int]' = OrderedDict()
        self._held: dict[int, list[bytes]] = {}  # rid -> digests referenced
        self.stats = {
            'hit_blocks': 0, 'evictions': 0, 'inserts': 0,
            # First-writer-wins losses: a second request prefilled the same
            # block before this insert landed. Mixed serving windows stretch
            # a prompt's prefill over several windows (blocks adopted only at
            # the final chunk), so same-prefix requests admitted meanwhile
            # prefill private duplicates — this counts that lost sharing.
            'insert_dupes': 0,
        }

    # ------------------------------------------------------------- lookup
    def match(self, digests: Sequence[bytes]) -> list[int]:
        """Block ids of the longest cached prefix of ``digests`` (no ref)."""
        blocks: list[int] = []
        for digest in digests:
            entry = self._entries.get(digest)
            if entry is None:
                break
            blocks.append(entry.block_id)
        return blocks

    def acquire(self, rid: int, digests: Sequence[bytes]) -> list[int]:
        """Longest-prefix match + incref each matched block for ``rid``."""
        blocks: list[int] = []
        matched: list[bytes] = []
        for digest in digests:
            entry = self._entries.get(digest)
            if entry is None:
                break
            entry.refcount += 1
            entry.holders.add(rid)
            self._evictable.pop(digest, None)
            matched.append(digest)
            blocks.append(entry.block_id)
        if matched:
            self._held.setdefault(rid, []).extend(matched)
        self.stats['hit_blocks'] += len(blocks)
        self._publish()
        return blocks

    # ------------------------------------------------------------- insert
    def insert(self, rid: int, digest: bytes, block_id: int) -> bool:
        """Adopt ``block_id`` for ``digest``; ``rid`` holds the first ref.

        False when the digest is already cached — the caller's physical
        block stays private to it (freed by the scheduler at finish).
        """
        if digest in self._entries:
            self.stats['insert_dupes'] += 1
            return False
        self._entries[digest] = _CacheEntry(
            block_id, refcount=1, holders={rid}
        )
        self._held.setdefault(rid, []).append(digest)
        self.stats['inserts'] += 1
        self._publish()
        return True

    # ------------------------------------------------------------ release
    def release(self, rid: int) -> None:
        """Drop every reference ``rid`` holds (finish/abort path)."""
        for digest in self._held.pop(rid, []):
            entry = self._entries.get(digest)
            if entry is None:
                continue  # evicted while... cannot happen (ref pinned)
            entry.refcount -= 1
            entry.holders.discard(rid)
            if entry.refcount <= 0:
                # Most-recently released = most likely to be reused next:
                # append to the MRU end.
                self._evictable[digest] = entry.block_id
        self._publish()

    # ------------------------------------------------------------- evict
    def evict(self, max_blocks: int) -> list[int]:
        """Pop up to ``max_blocks`` LRU evictable blocks; caller returns
        them to the scheduler free list."""
        return [bid for _, bid in self.evict_entries(max_blocks)]

    def evict_entries(self, max_blocks: int) -> list[tuple[bytes, int]]:
        """``evict`` but returning ``(digest, block_id)`` pairs, so the
        engine can spill the evicted blocks' KV into the host tier
        (``HostKVTier``) before the blocks rejoin the free list. Eviction
        is never silent: every popped block counts into the per-tier
        eviction series (``distllm_prefix_tier_evictions_total{tier=hbm}``)
        whether or not a lower tier catches it — the caller records the
        final-drop counter when no tier exists."""
        evicted: list[tuple[bytes, int]] = []
        while self._evictable and len(evicted) < max_blocks:
            digest, block_id = self._evictable.popitem(last=False)
            del self._entries[digest]
            evicted.append((digest, block_id))
        if evicted:
            from distllm_tpu.observability import instruments as _m

            _m.PREFIX_EVICTIONS.inc(len(evicted))
            _m.PREFIX_TIER_EVICTIONS.labels(tier='hbm').inc(len(evicted))
        self.stats['evictions'] += len(evicted)
        self._publish()
        return evicted

    # -------------------------------------------------------------- state
    @property
    def num_cached(self) -> int:
        return len(self._entries)

    @property
    def num_evictable(self) -> int:
        return len(self._evictable)

    def num_sole(self, rid: int) -> int:
        """Blocks only ``rid`` references: what its ``release`` would make
        evictable (the decode-budget walk counts them as capacity that
        comes back when the request finishes)."""
        return sum(
            1 for digest in self._held.get(rid, ())
            if self._entries[digest].refcount == 1
        )

    @property
    def num_shared(self) -> int:
        return sum(1 for e in self._entries.values() if len(e.holders) >= 2)

    def _publish(self) -> None:
        from distllm_tpu.observability import instruments as _m

        _m.PREFIX_CACHED_BLOCKS.set(self.num_cached)
        _m.PREFIX_EVICTABLE_BLOCKS.set(self.num_evictable)
        _m.PREFIX_SHARED_BLOCKS.set(self.num_shared)


def encode_kvblock(
    k: np.ndarray,
    v: np.ndarray,
    k_scale: np.ndarray | None = None,
    v_scale: np.ndarray | None = None,
) -> bytes:
    """Serialize one block's KV (plus int8 scale rows) as ``.kvblock`` v2.

    One JSON header line carrying shape/dtype (and the optional scales
    entry), then the raw K bytes followed by the raw V bytes (then
    K-scale, V-scale) at exact byte offsets — byte-exact for bf16 and
    every other KV dtype, no pickle. The SAME payload serves as the disk
    tier's file format and the peer tier's wire format: a sibling
    replica's fetch and a process restart read identical bytes."""
    meta = {'version': 2, 'shape': list(k.shape), 'dtype': str(k.dtype)}
    meta['scales'] = (
        None if k_scale is None
        else {'shape': list(k_scale.shape), 'dtype': str(k_scale.dtype)}
    )
    # Compact separators: the header rides every spilled block.
    header = json.dumps(meta, separators=(',', ':')).encode() + b'\n'
    payload = header + k.tobytes() + v.tobytes()
    if k_scale is not None:
        payload += k_scale.tobytes() + v_scale.tobytes()
    return payload


def decode_kvblock(payload: bytes) -> tuple[np.ndarray, ...]:
    """Parse a ``.kvblock`` payload back into ``(K, V)`` — or ``(K, V,
    K_scale, V_scale)`` for a quantized spill.

    Raises ``ValueError``/``KeyError``/``TypeError`` on corruption (bad
    header, short read, trailing bytes, unknown version): callers — the
    disk tier's file read, the peer tier's fabric fetch — must degrade
    the failure to a counted tier error + miss, never let it reach
    ``add_request``."""
    header, sep, body = payload.partition(b'\n')
    if not sep:
        raise ValueError('missing header line')
    meta = json.loads(header)
    version = int(meta.get('version', 1))
    if version > 2:
        # A newer process wrote a layout this reader does not
        # understand; halving the body blindly would hand the
        # attention kernel another format's bytes as KV.
        raise ValueError(f'unknown .kvblock version {version}')
    # jnp.dtype resolves 'bfloat16' through ml_dtypes into a
    # numpy-compatible dtype, so the round trip is byte-exact for
    # bf16 KV.
    dtype = np.dtype(jnp.dtype(meta['dtype']))
    shape = tuple(int(d) for d in meta['shape'])
    if version < 2:
        # Version-less pre-int8 spill: body is exactly K then V.
        half = len(body) // 2
        k = np.frombuffer(body[:half], dtype=dtype).reshape(shape)
        v = np.frombuffer(body[half:], dtype=dtype).reshape(shape)
        return k, v
    # v2: exact byte offsets from the header (never len//2 — the
    # optional scale tail would skew the split).
    scales_meta = meta.get('scales')
    arrays: list[np.ndarray] = []
    offset = 0
    specs = [(shape, dtype), (shape, dtype)]
    if scales_meta is not None:
        s_dtype = np.dtype(jnp.dtype(scales_meta['dtype']))
        s_shape = tuple(int(d) for d in scales_meta['shape'])
        specs += [(s_shape, s_dtype), (s_shape, s_dtype)]
    for a_shape, a_dtype in specs:
        count = int(np.prod(a_shape)) * a_dtype.itemsize
        chunk = body[offset:offset + count]
        if len(chunk) != count:
            raise ValueError('truncated .kvblock body')
        arrays.append(
            np.frombuffer(chunk, dtype=a_dtype).reshape(a_shape)
        )
        offset += count
    if offset != len(body):
        raise ValueError('trailing bytes in .kvblock body')
    return tuple(arrays)


class DiskKVTier:
    """Digest-keyed KV block files: the persistence tier under the host
    pool (docs/prefix_caching.md "Tier hierarchy").

    One ``<digest-hex>.kvblock`` file per spilled block (a JSON header
    line carrying shape/dtype, then the raw K bytes followed by the raw V
    bytes — byte-exact for bf16 and every other KV dtype, no pickle).
    Format version 2 adds a ``version`` field and a ``scales`` entry to
    the header so quantized (int8) pools spill their per-block scale rows
    alongside the data: the body becomes K, V, K-scale, V-scale at exact
    byte offsets computed from the header shapes. Version-less files
    (pre-int8 spills) still load on the legacy halve-the-body path;
    an UNKNOWN version counts ``distllm_prefix_tier_errors_total{tier=
    "disk"}`` and degrades to a miss (cold prefill) exactly like the
    other corruption paths — a newer process's format must never crash
    an older reader.
    The digest chain makes the file name self-describing: it identifies
    the ENTIRE token prefix up to and including the block, so a fresh
    engine on the same corpus promotes straight from a previous process's
    spills (cold-start warm TTFT). Bounded by ``max_bytes`` with LRU on
    use order; the on-disk index is rebuilt from file mtimes at
    construction. Thread-safe: the engine loop and server threads may
    race lookups against spills.
    """

    _SUFFIX = '.kvblock'

    def __init__(self, root: str | os.PathLike, max_bytes: int) -> None:
        self._lock = threading.Lock()
        self._root = Path(root)
        self._root.mkdir(parents=True, exist_ok=True)
        self.max_bytes = int(max_bytes)
        # hex digest -> file size, LRU order (oldest first), rebuilt from
        # mtimes so restarts keep the eviction order roughly honest.
        self._index: 'OrderedDict[str, int]' = OrderedDict()  # guarded by self._lock
        self._bytes = 0  # guarded by self._lock
        entries = []
        for path in self._root.glob(f'*{self._SUFFIX}'):
            try:
                stat = path.stat()
            # distlint: disable=swallowed-exception -- benign glob/stat race (a concurrent eviction unlinked the file); the index simply never learns it
            except OSError:
                continue
            entries.append((stat.st_mtime, path.stem, stat.st_size))
        for _, hexdigest, size in sorted(entries):
            self._index[hexdigest] = size
            self._bytes += size
        self._evict_over_budget_locked()
        self._publish_locked()

    def _path(self, hexdigest: str) -> Path:
        return self._root / f'{hexdigest}{self._SUFFIX}'

    # Called with self._lock held by every mutating public method.
    def _evict_over_budget_locked(self) -> int:  # guarded by self._lock
        dropped = 0
        while self._bytes > self.max_bytes and self._index:
            hexdigest, size = self._index.popitem(last=False)
            self._bytes -= size
            try:
                self._path(hexdigest).unlink()
            except OSError:
                # An eviction that cannot delete its file leaks disk
                # bytes outside the budget — counted, never silent.
                from distllm_tpu.observability import instruments as _im

                _im.PREFIX_TIER_ERRORS.labels(tier='disk').inc()
            dropped += 1
        if dropped:
            from distllm_tpu.observability import instruments as _m

            # Disk is the lowest tier: its evictions ARE final drops —
            # the prefix must re-prefill on its next arrival.
            _m.PREFIX_TIER_EVICTIONS.labels(tier='disk').inc(dropped)
            _m.PREFIX_TIER_DROPPED_BLOCKS.inc(dropped)
        return dropped

    def _publish_locked(self) -> None:  # guarded by self._lock
        from distllm_tpu.observability import instruments as _m

        _m.PREFIX_TIER_BYTES.labels(tier='disk').set(self._bytes)

    def contains(self, digest: bytes) -> bool:
        with self._lock:
            return digest.hex() in self._index

    def _drop_entry(self, hexdigest: str, *, unlink: bool = False) -> None:
        """Forget one indexed entry (IO error / corruption path) and count
        the tier error — a bad file must degrade to a miss, never raise
        into ``add_request``'s tier walk. The error is counted ONLY when
        the entry was still indexed: a read racing a concurrent eviction
        (file unlinked, index popped between get()'s lock release and its
        read) is the documented-benign miss, and counting it would let a
        perfectly healthy tier under eviction pressure read as sick."""
        from distllm_tpu.observability import instruments as _m

        with self._lock:
            size = self._index.pop(hexdigest, None)
            if size is not None:
                self._bytes -= size
                self._publish_locked()
        if unlink:
            try:
                os.unlink(self._path(hexdigest))
            # distlint: disable=swallowed-exception -- best-effort cleanup of a file already counted as a tier error below; a second unlink failure adds no signal
            except OSError:
                pass
        if size is not None:
            _m.PREFIX_TIER_ERRORS.labels(tier='disk').inc()

    def put(
        self,
        digest: bytes,
        k: np.ndarray,
        v: np.ndarray,
        k_scale: np.ndarray | None = None,
        v_scale: np.ndarray | None = None,
    ) -> bool:
        """Persist one block's KV (plus its quantization scales when the
        pool is int8); False when already present (the file contents are
        digest-determined, so rewriting buys nothing)."""
        from distllm_tpu.resilience.faults import get_fault_injector

        hexdigest = digest.hex()
        payload = encode_kvblock(k, v, k_scale, v_scale)
        with self._lock:
            if hexdigest in self._index:
                self._index.move_to_end(hexdigest)
                return False
            path = self._path(hexdigest)
            tmp = path.with_suffix('.tmp')
            try:
                get_fault_injector().fail_io('tier_io')
                tmp.write_bytes(payload)
                os.replace(tmp, path)
            except OSError:
                # Full/read-only disk degrades to no tier — counted, so
                # a silently-dead persistence tier shows up in scrapes.
                from distllm_tpu.observability import instruments as _m

                _m.PREFIX_TIER_ERRORS.labels(tier='disk').inc()
                return False
            self._index[hexdigest] = len(payload)
            self._bytes += len(payload)
            from distllm_tpu.observability import instruments as _m

            _m.PREFIX_TIER_SPILLS.labels(tier='disk').inc()
            self._evict_over_budget_locked()
            self._publish_locked()
        return True

    def get(self, digest: bytes) -> tuple[np.ndarray, ...] | None:
        """Load one block's host arrays — ``(K, V)``, or ``(K, V,
        K_scale, V_scale)`` for a quantized spill — refreshing its LRU
        slot. The file read happens OUTSIDE the lock — contains() runs on
        the admission path and must not stall behind multi-megabyte
        cold-disk reads. A concurrent eviction racing the read is just a
        miss. A corrupt or truncated file (bad header, short read — a
        torn spill from a killed process, bit rot, or a foreign file
        wearing the suffix) and an unknown ``version`` alike count a
        ``distllm_prefix_tier_errors_total{tier="disk"}``, drop the
        entry, and return None: the caller falls through to cold
        prefill, never an exception in ``add_request``."""
        from distllm_tpu.resilience.faults import get_fault_injector

        hexdigest = digest.hex()
        with self._lock:
            if hexdigest not in self._index:
                return None
            self._index.move_to_end(hexdigest)
        try:
            get_fault_injector().fail_io('tier_io')
            payload = self._path(hexdigest).read_bytes()
        # distlint: disable=swallowed-exception -- degradation is counted: _drop_entry increments distllm_prefix_tier_errors_total{tier="disk"}
        except OSError:
            self._drop_entry(hexdigest)
            return None
        try:
            return decode_kvblock(payload)
        # distlint: disable=swallowed-exception -- degradation is counted: _drop_entry increments distllm_prefix_tier_errors_total{tier="disk"} and unlinks the corrupt file
        except (ValueError, KeyError, TypeError):
            self._drop_entry(hexdigest, unlink=True)
            return None

    @property
    def num_blocks(self) -> int:
        with self._lock:
            return len(self._index)

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes


class PeerKVTier:
    """Sibling replicas' spilled KV blocks, fetched over the zmq fabric —
    the tier between disk and drop (docs/routing.md "Peer KV tier").

    Each peer endpoint is a sibling replica's
    :class:`~distllm_tpu.parallel.fabric.KVBlockServer`, answering
    digest-keyed HAS/GET with the same ``.kvblock`` v2 payload the disk
    tier persists (:func:`encode_kvblock`): content-addressed KV handoff,
    no new wire format. Purely a READ tier — spills never write here
    (each replica owns its own spill budget); a fetched block re-enters
    the local host pool like a disk promotion. Every failure degrades:
    an unreachable peer backs off ``failure_backoff_s`` and the lookup
    misses (cold prefill), a corrupt payload counts
    ``distllm_prefix_tier_errors_total{tier="peer"}`` — the serving loop
    never sees a network exception. Endpoints may be added after
    construction (``add_endpoint``): sibling ports are usually unknown
    until every replica has bound its serve socket.
    """

    def __init__(
        self,
        endpoints: Sequence[str] = (),
        *,
        timeout_ms: int = 500,
        failure_backoff_s: float = 5.0,
    ) -> None:
        # Lazy fabric import: kv_cache must stay importable without zmq
        # reaching module scope (mirrors the tiers' lazy instruments).
        from distllm_tpu.parallel.fabric import KVBlockClient

        self._lock = threading.Lock()
        self.endpoints: list[str] = list(endpoints)  # guarded by self._lock
        self.failure_backoff_s = float(failure_backoff_s)
        self._client = KVBlockClient(timeout_ms=timeout_ms)
        # endpoint -> monotonic instant its backoff expires.
        self._backoff_until: dict[str, float] = {}  # guarded by self._lock
        # Tiny digest -> endpoint memo so get() asks the peer contains()
        # just saw first, instead of re-probing every sibling.
        self._hit_memo: 'OrderedDict[bytes, str]' = OrderedDict()  # guarded by self._lock
        self.fetched_blocks = 0
        self.fetched_bytes = 0

    def add_endpoint(self, endpoint: str) -> None:
        with self._lock:
            if endpoint not in self.endpoints:
                self.endpoints.append(endpoint)

    def _live_endpoints(self) -> list[str]:
        now = time.monotonic()
        with self._lock:
            return [
                ep for ep in self.endpoints
                if self._backoff_until.get(ep, 0.0) <= now
            ]

    def _note_failure(self, endpoint: str) -> None:
        from distllm_tpu.observability import instruments as _m

        _m.PREFIX_TIER_ERRORS.labels(tier='peer').inc()
        with self._lock:
            self._backoff_until[endpoint] = (
                time.monotonic() + self.failure_backoff_s
            )

    def _memo(self, digest: bytes, endpoint: str) -> None:
        with self._lock:
            self._hit_memo[digest] = endpoint
            self._hit_memo.move_to_end(digest)
            while len(self._hit_memo) > 1024:
                self._hit_memo.popitem(last=False)

    def contains(self, digest: bytes) -> bool:
        """Membership across live peers (first hit wins, memoized for the
        ``get`` that follows). Network probes on the admission path are
        bounded by the client timeout and the per-peer backoff."""
        from distllm_tpu.parallel.fabric import KV_HIT

        for endpoint in self._live_endpoints():
            reply = self._client.request(endpoint, b'HAS', digest)
            if reply is None:
                self._note_failure(endpoint)
                continue
            if reply[0] == KV_HIT:
                self._memo(digest, endpoint)
                return True
        return False

    def get(self, digest: bytes) -> tuple[np.ndarray, ...] | None:
        """Fetch one block's host arrays from a sibling replica, memoized
        endpoint first. A hit lands a ``peer_fetch`` flight record (the
        fabric twin of the promotion path's ``promote``); every failure
        mode — timeout, MISS, corrupt payload — returns None so the
        caller degrades to cold prefill."""
        from distllm_tpu.observability import instruments as _m
        from distllm_tpu.observability.flight import get_flight_recorder
        from distllm_tpu.parallel.fabric import KV_HIT

        with self._lock:
            memo = self._hit_memo.get(digest)
        ordered = self._live_endpoints()
        if memo in ordered:
            ordered.remove(memo)
            ordered.insert(0, memo)
        for endpoint in ordered:
            t_start = time.monotonic()
            reply = self._client.request(endpoint, b'GET', digest)
            if reply is None:
                self._note_failure(endpoint)
                continue
            status, payload = reply
            if status != KV_HIT:
                continue  # evicted on the sibling since the HAS probe
            try:
                arrays = decode_kvblock(payload)
            except (ValueError, KeyError, TypeError):
                # Counted degradation; the caller falls through to cold
                # prefill (docs/routing.md "Peer KV tier").
                _m.PREFIX_TIER_ERRORS.labels(tier='peer').inc()
                continue
            fetch_s = time.monotonic() - t_start
            self.fetched_blocks += 1
            self.fetched_bytes += len(payload)
            get_flight_recorder().record(
                'peer_fetch',
                endpoint=endpoint,
                blocks=1,
                bytes=len(payload),
                fetch_s=round(fetch_s, 6),
            )
            return arrays
        return None

    def close(self) -> None:
        self._client.close()


class HostKVTier:
    """Bounded digest-keyed host-RAM pool of spilled KV blocks — the tier
    between the HBM prefix cache and the (optional) disk tier.

    The engine spills evicted ref==0 cache blocks here (one device→host
    fetch per eviction batch) instead of dropping their KV; a later
    same-prefix arrival promotes them back into the paged pool via async
    ``jax.device_put`` (engine ``_begin_promotion``). Entries are whole
    per-block KV slices (``[L, block_size, N_kv, Hd]`` each for K and V;
    quantized pools append the two ``[L, N_kv]`` fp32 scale slices) keyed
    by the chained block digest, LRU-ordered, bounded by ``max_bytes``.
    With a :class:`DiskKVTier` attached, spills write THROUGH to disk
    (persistence never depends on host-LRU timing) and host misses fall
    through to disk, pulling hits back into the host pool. With a
    :class:`PeerKVTier` attached, the fallthrough extends one hop
    further — host → disk → peer — and a peer hit re-enters the host
    pool the same way (docs/routing.md). Thread-safe for the same reason
    as the disk tier.
    """

    def __init__(
        self,
        max_bytes: int,
        disk: DiskKVTier | None = None,
        peer: 'PeerKVTier | None' = None,
    ) -> None:
        self._lock = threading.Lock()
        self.max_bytes = int(max_bytes)
        self.disk = disk
        self.peer = peer
        # digest -> (k, v[, k_scale, v_scale]) host arrays, LRU order
        # (oldest first). Arity follows what was spilled: the tier never
        # inspects payloads beyond byte accounting.
        self._entries: 'OrderedDict[bytes, tuple[np.ndarray, ...]]' = (
            OrderedDict()
        )  # guarded by self._lock
        self._bytes = 0  # guarded by self._lock

    def _publish_locked(self) -> None:  # guarded by self._lock
        from distllm_tpu.observability import instruments as _m

        _m.PREFIX_TIER_BYTES.labels(tier='host').set(self._bytes)

    def _evict_over_budget_locked(self) -> None:  # guarded by self._lock
        from distllm_tpu.observability import instruments as _m

        while self._bytes > self.max_bytes and self._entries:
            digest, arrays = self._entries.popitem(last=False)
            self._bytes -= sum(a.nbytes for a in arrays)
            _m.PREFIX_TIER_EVICTIONS.labels(tier='host').inc()
            # Write-through at put() time normally persisted the block,
            # but a full/read-only disk degrades put() to a no-op — so
            # the drop decision checks what the disk actually HOLDS, not
            # what was attempted. Lock order host→disk only (the disk
            # tier never takes the host lock), so this cannot deadlock.
            if self.disk is None or not self.disk.contains(digest):
                _m.PREFIX_TIER_DROPPED_BLOCKS.inc()

    def lookup(self, digest: bytes) -> str | None:
        """Which tier holds ``digest``
        (``'host'``/``'disk'``/``'peer'``/None), with hit/miss
        accounting. Pure membership — no load, no LRU touch — so
        ``add_request``'s promotion-planning walk stays cheap (the peer
        hop is a bounded-timeout fabric probe, consulted last)."""
        from distllm_tpu.observability import instruments as _m

        with self._lock:
            if digest in self._entries:
                _m.PREFIX_TIER_HITS.labels(tier='host').inc()
                return 'host'
        if self.disk is not None and self.disk.contains(digest):
            _m.PREFIX_TIER_HITS.labels(tier='disk').inc()
            return 'disk'
        if self.peer is not None and self.peer.contains(digest):
            _m.PREFIX_TIER_HITS.labels(tier='peer').inc()
            return 'peer'
        lowest = (
            'peer' if self.peer is not None
            else 'disk' if self.disk is not None
            else 'host'
        )
        _m.PREFIX_TIER_MISSES.labels(tier=lowest).inc()
        return None

    def contains_local(self, digest: bytes) -> bool:
        """Metric-free host/disk membership — the KVBlockServer's HAS
        answer. A sibling's probe must not skew THIS replica's tier
        hit/miss accounting, and must never recurse into this replica's
        own peer tier (two replicas would ping-pong a miss forever)."""
        with self._lock:
            if digest in self._entries:
                return True
        return self.disk is not None and self.disk.contains(digest)

    def encoded_local(self, digest: bytes) -> bytes | None:
        """One block as ``.kvblock`` payload from the LOCAL host/disk
        tiers only — the KVBlockServer's GET answer (serve side of the
        peer hop; peer recursion excluded for the same reason as
        ``contains_local``)."""
        arrays = self.get(digest, allow_peer=False)
        if arrays is None:
            return None
        return encode_kvblock(*arrays)

    def put(
        self,
        digest: bytes,
        k: np.ndarray,
        v: np.ndarray,
        k_scale: np.ndarray | None = None,
        v_scale: np.ndarray | None = None,
    ) -> bool:
        """Adopt one spilled block (host copies of its K/V slices, plus
        the per-block scale rows for a quantized pool)."""
        from distllm_tpu.observability import instruments as _m

        arrays = (
            (k, v) if k_scale is None else (k, v, k_scale, v_scale)
        )
        if self.disk is not None:
            self.disk.put(digest, k, v, k_scale, v_scale)
        with self._lock:
            if digest in self._entries:
                self._entries.move_to_end(digest)
                return False
            self._entries[digest] = arrays
            self._bytes += sum(a.nbytes for a in arrays)
            _m.PREFIX_TIER_SPILLS.labels(tier='host').inc()
            self._evict_over_budget_locked()
            self._publish_locked()
        return True

    def get(
        self, digest: bytes, *, allow_peer: bool = True
    ) -> tuple[np.ndarray, ...] | None:
        """``(K, V)`` — or ``(K, V, K_scale, V_scale)`` for a quantized
        spill — for ``digest``, refreshing its LRU slot; host misses fall
        through to the disk tier, then (``allow_peer``) to the peer tier,
        and a lower-tier hit re-enters the host pool (a promoted prefix
        is about to be hot again)."""
        with self._lock:
            entry = self._entries.get(digest)
            if entry is not None:
                self._entries.move_to_end(digest)
                return entry
        loaded = source = None
        if self.disk is not None:
            loaded = self.disk.get(digest)
            if loaded is not None:
                source = 'disk'
        if loaded is None and allow_peer and self.peer is not None:
            loaded = self.peer.get(digest)
            if loaded is not None:
                source = 'peer'
        if loaded is None:
            return None
        from distllm_tpu.observability import instruments as _m

        _m.PREFIX_TIER_PROMOTIONS.labels(tier=source).inc()
        with self._lock:
            if digest not in self._entries:
                self._entries[digest] = loaded
                self._bytes += sum(a.nbytes for a in loaded)
                self._evict_over_budget_locked()
                self._publish_locked()
        return loaded

    @property
    def num_blocks(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes


class _PoolView:
    """``PagedKVCache.k`` / ``.v``: what a HOST reader indexes, a layer and
    then block ids, ``kv.k[layer][block_ids]``, giving those blocks on the
    host in the logical shape ``[.., block_size, N_kv, Hd]`` (a
    ``QuantizedKV`` of such blocks and their ``[.., N_kv]`` scales for an
    int8 pool). Only the blocks asked for are gathered (one device gather,
    as indexing a layer's buffer was; they are unfolded on the host), never
    a buffer: a pool may fill the device. The programs' operands are
    ``k_pool`` / ``v_pool``. ``lanes`` keeps a row's leading lanes: a
    latent pool's ``.v`` is its one plane read so."""

    def __init__(
        self, cache: 'PagedKVCache', pool, layer=None, lanes=None
    ) -> None:
        self._cache = cache
        self._pool = pool
        self._layer = layer
        self._lanes = lanes

    def __len__(self) -> int:
        return self._cache.shape[0 if self._layer is None else 1]

    def __getitem__(self, index):
        from distllm_tpu.ops.paged_attention import QuantizedKV, unfold_heads

        if self._layer is None:
            layer = range(len(self))[index]
            return _PoolView(self._cache, self._pool, layer, self._lanes)
        blocks = self._gather(self._pool, self._layer, np.asarray(index))
        num_kv_heads = self._cache.shape[3]
        if self._lanes is not None:
            return np.asarray(blocks)[..., None, :self._lanes]
        if self._cache.quantized:
            return QuantizedKV(
                unfold_heads(np.asarray(blocks.data), num_kv_heads),
                np.asarray(blocks.scale),
            )
        return unfold_heads(np.asarray(blocks), num_kv_heads)

    def _gather(self, pool, layer: int, block_ids):
        """The blocks ``block_ids`` of ``layer`` as the pool stores them."""
        if self._cache.latent:  # a plane a layer
            return pool[layer][block_ids]
        return jax.tree.map(lambda c: c[layer, block_ids], pool)


class PagedKVCache:
    """Device-resident paged K/V arrays (pure container).

    ``k_pool`` and ``v_pool`` are the arrays the serving programs take and
    give back, each ONE stacked array ``pool_shape = [L, num_blocks,
    block_size, N_kv * Hd]``; ``shape`` stays the logical 5-tuple, and ``k``
    / ``v`` are the host reader's view (:class:`_PoolView`).

    Block *accounting* — who owns which block, admission, preemption — is
    the scheduler's job (``engine/scheduler.py`` over the native C++ core);
    keeping a second free-list here would silently desync from it.

    With ``dtype='int8'`` each pool array is a
    :class:`~distllm_tpu.ops.paged_attention.QuantizedKV` — int8 data of
    the same stored shape plus a per-block-per-KV-head fp32 scale array
    ``[num_layers, num_blocks, num_kv_heads]`` (docs/serving.md
    "Quantized KV cache"). QuantizedKV is a NamedTuple pytree, so every
    jitted engine path that treats the pool as an opaque carry (scan,
    donation, COW gathers) works unchanged; only code that quantizes,
    dequantizes, or inspects ``.shape`` dispatches on the container.

    With ``row`` (a latent group, ``models.common.PagedGroup``) a layer
    holds ONE plane of ``[num_blocks, block_size, row]`` (``row`` already in
    whole lane tiles): ``k_pool`` is the tuple of the layers' planes (not
    stacked: ``models/deepseek_v3.py`` says why), ``v_pool`` is ``()``, and
    the host's ``v`` view reads the first ``value_lanes`` lanes of ``k``'s
    rows. ``shape`` is the planes', one KV head of ``row``.
    """

    def __init__(
        self,
        num_layers: int,
        num_blocks: int,
        block_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: str = 'bfloat16',
        sharding=None,
        lazy: bool = False,
        row: int | None = None,
        value_lanes: int | None = None,
    ) -> None:
        self.value_lanes = value_lanes if row is not None else None
        if row is not None:
            num_kv_heads, head_dim = 1, row
        self.latent = row is not None
        self.shape = (num_layers, num_blocks, block_size, num_kv_heads, head_dim)
        self.pool_shape = (
            num_layers, num_blocks, block_size, num_kv_heads * head_dim
        )
        self.dtype = jnp.dtype(dtype)
        self.quantized = self.dtype == jnp.dtype(jnp.int8)
        if self.latent and (self.quantized or sharding is not None):
            raise ValueError(
                'a pool of latent rows has no int8 and no sharded form yet'
            )
        # Symmetric per-block-per-KV-head scales: one fp32 per (layer,
        # block, kv head), for K and V independently (the two pool arrays
        # each carry their own scale plane — the ``[L, blocks, 2, nkv]``
        # layout realized as its K/V halves).
        self.scale_shape = (num_layers, num_blocks, num_kv_heads)
        self._sharding = sharding
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.k_pool = None
        self.v_pool = None
        if not lazy:
            self.allocate()

    @classmethod
    def for_group(
        cls, group, model_cfg, num_blocks: int, block_size: int, **kw
    ) -> 'PagedKVCache':
        """The pool of one paged group (``models.common.PagedGroup``) of a
        model: a latent group declares its row, a K/V group's is the
        model's ``num_kv_heads * head_size``."""
        return cls(
            group.num_layers, num_blocks, block_size, model_cfg.num_kv_heads,
            model_cfg.head_size, row=group.stored_row,
            value_lanes=group.value_lanes, **kw,
        )

    @property
    def k(self) -> _PoolView:
        return _PoolView(self, self.k_pool)

    @property
    def v(self) -> _PoolView:
        if self.latent:
            return _PoolView(self, self.k_pool, lanes=self.value_lanes)
        return _PoolView(self, self.v_pool)

    def _zeros(self):
        from distllm_tpu.ops.paged_attention import QuantizedKV

        if self.latent:
            return tuple(
                jnp.zeros(self.pool_shape[1:], dtype=self.dtype)
                for _ in range(self.pool_shape[0])
            )

        if self._sharding is None:
            data = jnp.zeros(self.pool_shape, dtype=self.dtype)
        else:
            # Allocate directly into the sharded layout: under tensor
            # parallelism num_blocks is sized against AGGREGATE HBM, so a
            # transient full-size allocation on one device would OOM.
            data = jax.jit(
                lambda: jnp.zeros(self.pool_shape, dtype=self.dtype),
                out_shardings=self._sharding,
            )()
        if not self.quantized:
            return data
        # Scales are tiny (4 bytes per block per KV head — ~1/1024 of the
        # data plane) and are read by every device each dispatch, so they
        # stay replicated even when the data plane is sharded.
        return QuantizedKV(data, jnp.zeros(self.scale_shape, jnp.float32))

    def allocate(self) -> None:
        """Materialize the pool arrays (``lazy=True`` defers this so the
        engine can run transient-heavy weight migrations first)."""
        if self.k_pool is not None:
            return
        from distllm_tpu.observability import instruments

        self.k_pool = self._zeros()
        self.v_pool = () if self.latent else self._zeros()
        instruments.KV_HBM_BYTES.set(self.hbm_bytes)

    def spec(self, plane: str = 'k'):
        """Shape/dtype pytree for one pool array (AOT compilation input):
        a bare ShapeDtypeStruct, or a QuantizedKV of them when int8; a
        latent pool's planes, and ``()`` for the V plane it does not have."""
        if self.latent:
            return () if plane == 'v' else (
                jax.ShapeDtypeStruct(self.pool_shape[1:], self.dtype),
            ) * self.pool_shape[0]
        data = jax.ShapeDtypeStruct(self.pool_shape, self.dtype)
        if not self.quantized:
            return data
        from distllm_tpu.ops.paged_attention import QuantizedKV

        return QuantizedKV(
            data, jax.ShapeDtypeStruct(self.scale_shape, jnp.float32)
        )

    def blocks_needed(self, num_tokens: int) -> int:
        return (num_tokens + self.block_size - 1) // self.block_size

    @property
    def hbm_bytes(self) -> int:
        return int(sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.k_pool, self.v_pool))
        ))


class StatePool:
    """Device-resident fixed state of the sequences of a model with
    recurrent layers, beside their KV pages (pure container, like
    ``PagedKVCache``).

    ``spec`` is what ONE sequence holds (the model family's
    ``state_spec()``: a pytree of ``ShapeDtypeStruct``, one leaf per
    recurrent layer and kind of state); the pool is that tree with a leading
    ``[slots]`` on every leaf, each leaf a buffer of its own so that the
    decode window rewrites it whole and in place. A slot IS the scheduler's
    slot of a running sequence (the decode batch's row), so taking and
    freeing one is the scheduler's admission, finish and preemption; there
    is no second free-list here to fall out of step. A sequence's first
    prefill span starts from zero state whatever its slot held, and a pad
    row's slot (``slots``, one past the pool) is dropped on write.
    """

    def __init__(self, spec, slots: int, lazy: bool = False) -> None:
        self.slots = slots
        self.seq_spec = spec
        self.state = None
        if not lazy:
            self.allocate()

    def spec(self):
        """Shape/dtype pytree of the pool (AOT compilation input)."""
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((self.slots, *s.shape), s.dtype),
            self.seq_spec,
        )

    def allocate(self) -> None:
        if self.state is None:
            self.state = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), self.spec()
            )

    @property
    def bytes_per_slot(self) -> int:
        return int(sum(
            int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(self.seq_spec)
        ))

    @property
    def hbm_bytes(self) -> int:
        return self.slots * self.bytes_per_slot

    def describe(self) -> dict:
        """The pool as telemetry states it (``state_pool``): its slots and
        bytes, and of each kind of leaf of a sequence's state the count,
        shape and dtype (one kind for a model whose whole state is in its
        own dtype; a recurrent state in float32 is a second)."""
        kinds: dict[tuple, int] = {}
        for leaf in jax.tree.leaves(self.seq_spec):
            kind = (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
            kinds[kind] = kinds.get(kind, 0) + 1
        return {
            'slots': self.slots, 'bytes': self.hbm_bytes,
            'bytes_per_slot': self.bytes_per_slot,
            'leaves': [
                {'count': n, 'shape': list(shape), 'dtype': dtype}
                for (shape, dtype), n in kinds.items()
            ],
        }


def window_bound(window: int, block_size: int, span: int) -> int:
    """Most blocks a windowed sequence holds while ``span`` tokens of it are
    dispatched: those that ``window - 1 + span`` tokens touch at the worst
    alignment."""
    return -(-(window - 1 + max(1, span)) // block_size) + 1


class WindowBlocks:
    """Who holds which block of a WINDOWED paged group's pool (a
    ``models.common.PagedGroup`` with a ``window``): the free list and, for
    every sequence, the blocks a query of its next dispatch can still see.

    A query at position ``p`` of a windowed layer sees keys ``p - window < j
    <= p``. So before a dispatch whose queries of a sequence lie at
    positions ``[start, stop)``, :meth:`cover` frees every block wholly
    behind ``start - window + 1`` and allocates up to the block of ``stop -
    1``; the sequence's table row then carries the trash block (0) for every
    entry behind the window, which the paged kernel never fetches (it skips
    the chunks behind a window) and the XLA twin masks. Dispatches run on
    the device in the order they were issued, so a block freed while
    dispatch ``n + 1`` is planned is no longer read by anything issued
    before its next holder writes it.

    What a sequence holds is bounded whatever its length: ``bound(span)``
    blocks while a span of ``span`` tokens is dispatched. The engine gates
    admission on that constant (``scheduler.decode_budget_fits``, asked of
    this pool as of the scheduler's), so :meth:`cover` never runs short and
    nothing is preempted for this pool; the scheduler's preemption and
    finish release a sequence here too (:meth:`release`). Pure host state:
    the arrays are a ``PagedKVCache`` over the group's layers.
    """

    def __init__(self, num_blocks: int, block_size: int, window: int) -> None:
        if num_blocks < 2:
            raise ValueError('need >= 2 blocks (block 0 is reserved)')
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.window = window
        self._free = list(range(num_blocks - 1, 0, -1))
        # rid -> {index of the block in the sequence -> block id}
        self._rows: dict[int, dict[int, int]] = {}
        self.freed_total = 0

    def bound(self, span: int) -> int:
        """:func:`window_bound` of this pool's window and block size."""
        return window_bound(self.window, self.block_size, span)

    def first_visible_block(self, position: int) -> int:
        """Index of the block that holds the oldest key a query at
        ``position`` sees."""
        return max(0, position - self.window + 1) // self.block_size

    def cover(self, rid: int, start: int, stop: int) -> int:
        """Make ``rid`` hold exactly the blocks that queries at positions
        ``[start, stop)`` read or write; returns how many it gave back."""
        row = self._rows.setdefault(rid, {})
        lo = self.first_visible_block(start)
        hi = (max(stop, start + 1) - 1) // self.block_size
        freed = self._drop(row, [i for i in row if i < lo or i > hi])
        missing = [i for i in range(lo, hi + 1) if i not in row]
        if len(missing) > len(self._free):
            raise RuntimeError(
                f'windowed KV pool exhausted: sequence {rid} needs '
                f'{len(missing)} more blocks, {len(self._free)} are free '
                '(admission is gated on every sequence holding its bound: '
                'this is a bug, not load)'
            )
        for i in missing:
            row[i] = self._free.pop()
        return freed

    def trim_behind(self, rid: int, position: int) -> int:
        """Give back what a query at ``position`` (the sequence's next) no
        longer sees; returns how many blocks that was."""
        row = self._rows.get(rid)
        if not row:
            return 0
        lo = self.first_visible_block(position)
        return self._drop(row, [i for i in row if i < lo])

    def _drop(self, row: dict[int, int], indices: list[int]) -> int:
        for i in sorted(indices, reverse=True):
            self._free.append(row.pop(i))
        self.freed_total += len(indices)
        return len(indices)

    def release(self, rid: int) -> None:
        """The sequence finished, failed or was preempted: all of its
        blocks go back (a preempted one prefills again from position 0)."""
        row = self._rows.pop(rid, None)
        if row:
            self._free.extend(row[i] for i in sorted(row, reverse=True))

    def table_row(self, rid: int, out: np.ndarray) -> np.ndarray:
        """``rid``'s table row written into ``out`` (zeros): the block id
        at every index it holds, the trash block everywhere else."""
        for i, block in self._rows.get(rid, {}).items():
            if i < out.shape[0]:
                out[i] = block
        return out

    def held(self, rid: int) -> int:
        return len(self._rows.get(rid, ()))

    def ends(self, rid: int, tail: int) -> dict:
        """The two ends of what ``rid`` holds, for a finished request's
        flight record: the lowest table index it still holds with its
        block (the window's lower edge), and the block at index ``tail``
        (of the last position it wrote). Empty where it holds neither."""
        row = self._rows.get(rid)
        if not row or tail not in row:
            return {}
        first = min(row)
        return {
            'kv_window_first_index': first,
            'kv_window_first_block': row[first],
            'kv_window_tail_block': row[tail],
        }

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_held(self) -> int:
        return sum(len(row) for row in self._rows.values())
