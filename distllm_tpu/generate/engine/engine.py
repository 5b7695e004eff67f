"""Continuous-batching generation engine over the paged KV cache.

The TPU-native replacement for the vLLM offline engine the reference wraps
(``distllm/generate/generators/vllm_backend.py``; SURVEY.md section 2.4 N1):

- **prefill**: one sequence per call, bucketed prompt lengths (jit cache
  stays small), K/V scattered into that sequence's blocks;
- **decode**: ONE jitted dispatch generates a *window* of
  ``decode_steps`` tokens for the whole running batch at fixed shapes
  (``max_num_seqs`` slots) — a ``lax.scan`` of fused decode+sample steps
  in which each sampled token feeds the next step entirely on device
  (``models/mistral.py decode_loop``), so the host syncs once per
  window instead of once per token. ``generate_ids`` additionally
  pipelines ``pipeline_depth`` windows: the next window is dispatched
  before the previous window's tokens are fetched, so the host's fetch
  and scheduling overlap device compute; EOS is discovered one window
  late (bounded token waste, vLLM-style multi-step scheduling makes the
  same trade). What the window length and the pipeline depth are worth
  on the chip is not measured on today's code;
- **scheduler**: waiting → running admission under block budget, vLLM-style
  recompute preemption when the pool runs dry mid-decode — implemented as a
  NATIVE C++ core (``distllm_tpu/native/scheduler.cpp`` via
  ``engine/scheduler.py``, Python twin as fallback/oracle);
- requests join and leave the batch between steps — continuous batching.

The KV caches are donated through the jitted step so XLA updates them in
place in HBM (no per-step cache copies).
"""

from __future__ import annotations

import contextlib
import importlib
import itertools
import math
import threading
import time
from dataclasses import dataclass, field
from enum import Enum

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import field_validator, model_validator

from distllm_tpu.generate.engine.kv_cache import (
    WindowBlocks,
    window_bound,
    DiskKVTier,
    HostKVTier,
    PagedKVCache,
    PeerKVTier,
    PrefixCache,
    StatePool,
    block_digests,
)
from distllm_tpu.generate.engine.scheduler import (
    BudgetRow,
    InstrumentedScheduler,
    SchedulerExhausted,
    decode_budget_fits,
    make_scheduler,
)
from distllm_tpu.models import mistral, moe
from distllm_tpu.models.tokenizer import bucket_ladder, pick_bucket
from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.observability import steps as _steps
from distllm_tpu.observability import xla_cost as _xla_cost
from distllm_tpu.observability.flight import (
    get_flight_recorder,
    get_stall_watchdog,
)
from distllm_tpu.observability.startup import (
    get_compile_watcher,
    record_backend_init,
)
from distllm_tpu.ops.paged_attention import (
    KV_QUANT_MAX,
    QuantizedKV,
    fold_heads,
    quantize_kv_rows,
    unfold_heads,
    walk_block_form,
    walk_keys_a_step,
)
from distllm_tpu.ops.sampling import fold_row_keys, sample_tokens
from distllm_tpu.resilience.admission import (
    EngineLoadView,
    EngineOverloaded,
    shed_decision,
)
from distllm_tpu.resilience.faults import get_fault_injector
from distllm_tpu.utils import BaseConfig


@dataclass
class SamplingParams:
    """vLLM-parity sampling knobs (``vllm_backend.py:48-60``)."""

    temperature: float = 0.5
    top_p: float = 1.0
    min_p: float = 0.0
    # Per-request top-k over the served distribution (0 disables). Applied
    # as a rank mask intersected with top-p/min-p (ops/sampling.py).
    top_k: int = 0
    # Per-request sampling seed; None derives a stable per-request seed
    # from (EngineConfig.seed, request_id). Sampled output streams are
    # deterministic per (seed, schedule) — docs/speculative.md.
    seed: int | None = None
    max_tokens: int = 2000
    stop_token_ids: tuple[int, ...] = ()
    # A model that decides a block of positions together
    # (``CacheSpec.block``): a denoise step decides EVERY masked position
    # whose confidence is over this where those are more than its schedule's
    # count; None is the static rule (docs/serving.md "Blocks of positions").
    unmask_threshold: float | None = None


# Sentinel returned by _dispatch_window when nothing can be dispatched
# (every running slot's budget is covered by in-flight windows).
_DRAIN = object()


def _round_sig(value: float, digits: int = 4) -> float:
    """Round a utilization to significant digits, not decimal places: a
    slow window's MFU of 3e-6 must not read as 0.0 in the record."""
    return float(f'{value:.{digits}g}')


def _sampled_rows(temperature: np.ndarray) -> int:
    """Rows of a dispatch's temperature array that sample: what the
    device's ``any(temperature > 0)`` sees (0 = its ``argmax`` branch)."""
    return int(np.count_nonzero(temperature > 0))


def _request_seed(
    engine_seed: int, request_id: int, explicit: int | None
) -> int:
    """Resolve a request's uint32 sampling seed.

    An explicit ``SamplingParams.seed`` wins (masked to uint32); otherwise
    hash (engine seed, request id) so every request owns an independent
    stream while the whole run stays reproducible from ``EngineConfig.seed``
    and the admission order — the (seed, schedule) determinism contract
    (docs/speculative.md "Sampled verification").
    """
    import hashlib

    if explicit is not None:
        return explicit & 0xFFFFFFFF
    digest = hashlib.blake2s(
        f'{engine_seed}:{request_id}'.encode(), digest_size=4
    ).digest()
    return int.from_bytes(digest, 'little')


class RequestState(Enum):
    WAITING = 'waiting'
    RUNNING = 'running'
    FINISHED = 'finished'
    # Terminal quarantine (docs/resilience.md): the request's dispatches
    # kept failing past the retry budget, or it outlived
    # ``request_deadline_s``. Its blocks are freed, the error is recorded
    # on the request, and it never re-enters the scheduler.
    FAILED = 'failed'


@dataclass
class Request:
    request_id: int
    prompt_ids: list[int]
    params: SamplingParams
    state: RequestState = RequestState.WAITING
    output_ids: list[int] = field(default_factory=list)
    # --- automatic prefix caching (docs/prefix_caching.md) ---
    # Chained block digests of the prompt's full blocks (cache keys).
    digests: list[bytes] = field(default_factory=list)
    # Prompt tokens whose KV is already valid in cache blocks at prefill
    # time — prefill runs only on the tail past this point.
    num_cached_tokens: int = 0
    # Leading blocks of this request's row owned by the prefix cache
    # (mirrors the scheduler's borrowed-prefix count).
    num_borrowed_blocks: int = 0
    # Aligned full-cover hit: the final matched block is SHARED and the
    # last prompt token must be recomputed into a private copy of it
    # (copy-on-write, resolved at prefill dispatch).
    cow_src_block: int | None = None
    # --- host/disk KV tier (docs/prefix_caching.md "Tier hierarchy") ---
    # Digests found in the host (or disk) tier past the HBM match at
    # add_request: promoted back into the paged pool at admission via
    # async device_put; cleared once the promotion begins.
    promo_digests: list[bytes] = field(default_factory=list)
    # --- mixed serving windows (docs/serving.md) ---
    # Absolute token counts tracking a prefill tail riding decode windows:
    # target = tokens that must be prefilled (prompt + any recompute
    # outputs, set at enrollment), sent = dispatched in some window
    # (possibly still in flight), done = confirmed by a processed window.
    # The request joins decode plans only once done >= target (and its
    # first token was emitted by the final chunk's sample). All three stay
    # 0 outside mixed mode, which makes every request decode-ready.
    prefill_target: int = 0
    prefill_sent: int = 0
    prefill_done: int = 0
    # --- prompt-lookup speculative decoding (docs/speculative.md) ---
    # Per-request n-gram drafter (None = this row never drafts: draft_k
    # is 0 or spec_draft_source is 'none'). Sampled rows draft too —
    # device-side rejection sampling verifies their spans ("Sampled
    # verification"). The drafter's index covers prompt+output history,
    # which recompute preemption preserves, so it survives preemption
    # untouched.
    drafter: 'object | None' = None
    # Resolved per-request sampling seed (uint32 domain): the request's
    # explicit SamplingParams.seed, else a stable hash of
    # (EngineConfig.seed, request_id). Feeds the counter-based PRNG key
    # derivation in ops/sampling.py.
    sample_seed: int = 0
    # --- lifecycle timestamps (flight recorder, docs/observability.md) ---
    # monotonic seconds; 0.0 = not reached. t_admit/t_first_token keep
    # their FIRST value across recompute preemption: the client-visible
    # latencies are measured from enqueue, not from the retry.
    t_enqueue: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_finish: float = 0.0
    # --- prefill/preemption accounting ('request' flight record) ---
    # Counted where it happens: every prefill dispatch the request rode
    # (by route: dense, paged, chunk, or mixed for a chunk that rode a
    # decode window), the tokens it prefilled there (re-prefill after
    # preemption included), the seconds of those steps up to its first
    # token, and how often it was preempted.
    preemptions: int = 0
    prefill_tokens: int = 0
    prefill_first_s: float = 0.0
    routes: dict = field(default_factory=dict)
    # ``num_tokens`` at the latest admission (-1 = never admitted): while
    # it still reads the same, the prefill this admission owes has not
    # emitted its token (the decode-budget walk counts that token).
    admit_tokens: int = -1
    # The decode-budget look-ahead has made this request wait at least
    # once (counted once a request in ``_stats``).
    budget_deferred: bool = False
    # Propagated request id (the server's X-Request-Id), captured from
    # tracing.request_scope at add_request; carried on the 'request'
    # flight record so one id correlates server spans, engine lifecycle,
    # and the Perfetto request track (docs/observability.md).
    trace_id: str | None = None
    # --- crash-domain recovery (docs/resilience.md) ---
    # Why the request reached a terminal state: '' while live, 'stop' /
    # 'length' for normal finishes, 'timeout' for a request that
    # outlived request_deadline_s, 'dispatch_failed' for quarantine
    # after repeated dispatch failures. A FAILED request also records
    # the error text.
    finish_reason: str = ''
    error: str | None = None
    # Where blocks of positions are decided together: per output token the
    # denoise step it was decided at (on the 'request' flight record).
    decided_at: list[int] = field(default_factory=list)

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_ids) + len(self.output_ids)


class EngineConfig(BaseConfig):
    """Capacity knobs (vLLM analogues: ``max_num_seqs``, ``max_model_len``,
    ``block_size``, ``gpu_memory_utilization`` → ``num_blocks``)."""

    block_size: int = 16
    num_blocks: int = 256
    max_num_seqs: int = 8
    max_model_len: int = 1024
    prefill_min_bucket: int = 16
    # Admitted requests with the same length bucket prefill together in one
    # padded dispatch (vLLM batches prefills via max_num_batched_tokens);
    # batch dim is bucketed to powers of two up to this cap to bound the
    # jit cache.
    max_prefill_batch: int = 8
    # Upper bound on batch x bucket tokens per prefill dispatch (the vLLM
    # max_num_batched_tokens analogue); also bounds the number of compiled
    # prefill shapes per bucket.
    max_prefill_tokens: int = 2048
    # Governs the scheduler implementation (C++ core vs Python twin).
    prefer_native_allocator: bool = True
    # Paged-attention kernel selector for EVERY serving dispatch — decode
    # windows, paged/chunked prefill tails, mixed windows, and
    # speculative verify spans all route through the one
    # ops.paged_attention.ragged_paged_attention callsite
    # (docs/serving.md "Attention kernel backends"). 'xla' is the
    # always-available bit-exact baseline; 'pallas' is the fused TPU
    # kernel; 'interpret' runs the same kernel on the Pallas interpreter
    # (CPU parity tier); 'auto' resolves ONCE at engine construction
    # (pallas on TPU for CI-covered head dims, else xla) and is pinned
    # into the jitted serving functions like qmm_backend — a later
    # config/global change can never re-route live dispatches. The
    # RESOLVED value is surfaced in engine telemetry and the
    # distllm_engine_attn_backend_info metric.
    attn_backend: str = 'xla'  # 'auto' | 'xla' | 'pallas' | 'interpret'
    # Storage dtype of the paged KV pool (docs/serving.md "Quantized KV
    # cache"). 'auto' (default) keeps today's behavior bit-exactly: the
    # pool stores the model compute dtype — the structural baseline, the
    # spec_draft_source='none' discipline applied to KV storage. 'bf16' /
    # 'fp32' pin an explicit float pool (useful for A/Bs against 'auto');
    # 'int8' stores K/V as int8 with per-block-per-KV-head symmetric fp32
    # scales, quantized at write time and dequantized fused into the
    # attention kernels' per-band KV loads — half the bytes per paged-
    # attention dispatch and per tier spill/promotion. int8 raises the
    # Pallas sublane tile to 32, so the default block_size=16 serves int8
    # through the XLA backend ('auto' falls back quietly; an explicit
    # 'pallas' pin raises with the block_size=32 fix).
    kv_cache_dtype: str = 'auto'  # 'auto' | 'bf16' | 'fp32' | 'int8'
    quantization: str | None = None  # None | 'int8' | 'nf4' (weight-only)
    # Tokens generated per decode dispatch (the fused lax.scan window).
    # 1 restores per-token dispatch; >1 amortizes dispatch+sync latency.
    decode_steps: int = 8
    # A rank cap on every request (vLLM's top_k semantic, applied before
    # top-p): the tokens no smaller than the K-th largest logit stay, ties
    # with it included; probabilities keep the full-vocab normalizer.
    # Default 0 = no cap (reference parity: vLLM's top_k is off by
    # default). Not a speed setting: the sampler finds the kept set as a
    # value threshold and sorts nothing at any K (ops/sampling.py); a cap
    # adds a second search to the steps of the rows that have one.
    sampling_top_window: int = 0
    # A model that decides a block of positions together: denoise forwards
    # a block (a static of the one compiled window); 0 = the block's length,
    # a position a forward. Every block takes one more forward, which
    # commits its K/V. Read for no other model.
    denoise_steps: int = 0

    @field_validator(
        'sampling_top_window', 'prefill_chunk_tokens', 'denoise_steps',
        'max_window_prefill_tokens', 'draft_k', 'host_kv_tier_bytes',
        'disk_kv_tier_bytes', 'max_dispatch_retries', 'peer_kv_timeout_ms',
    )
    @classmethod
    def _non_negative_window(cls, v: int, info) -> int:
        if v < 0:
            raise ValueError(f'{info.field_name} must be >= 0')
        return v

    @field_validator(
        'request_deadline_s', 'retry_backoff_s', 'history_interval_s',
        'peer_kv_backoff_s',
    )
    @classmethod
    def _non_negative_seconds(cls, v: float, info) -> float:
        if v < 0:
            raise ValueError(f'{info.field_name} must be >= 0')
        return v

    @field_validator('spec_ngram')
    @classmethod
    def _ngram_at_least_one(cls, v: int, info) -> int:
        if v < 1:
            raise ValueError(f'{info.field_name} must be >= 1')
        return v

    @field_validator('max_window_prefill_seqs')
    @classmethod
    def _at_least_one_row(cls, v: int, info) -> int:
        if v < 1:
            raise ValueError(f'{info.field_name} must be >= 1')
        return v

    @model_validator(mode='after')
    def _mixed_batching_consistent(self):
        if self.enable_mixed_batching and self.defer_prefill:
            # Both features re-route prefill emission through the window
            # pipeline and their bookkeeping (carried-ids scatter vs chunk
            # plans) conflicts, and mixed batching attacks the same
            # prefill-serialization gap without defer_prefill's tiny
            # extra dispatches.
            raise ValueError(
                'enable_mixed_batching and defer_prefill are mutually '
                'exclusive: both re-route prefill emission through the '
                'window pipeline; disable one'
            )
        if self.enable_mixed_batching and self.max_window_prefill_tokens < 1:
            raise ValueError(
                'enable_mixed_batching needs max_window_prefill_tokens >= 1'
            )
        if self.enable_mixed_batching and not (
            self.enable_prefix_cache or self.prefill_chunk_tokens
        ):
            # Only paged-route tails (cache-hit tails / chunk-split spans)
            # ride windows; without either feature NOTHING can ever
            # enroll, yet warmup would still compile the whole mixed shape
            # ladder — multi-minute dead TPU time for a structurally inert
            # feature. Fail at config time instead of silently.
            raise ValueError(
                'enable_mixed_batching needs enable_prefix_cache and/or '
                'prefill_chunk_tokens: only cache-hit tails and chunked '
                'spans ride mixed windows (docs/serving.md)'
            )
        if self.draft_k and self.defer_prefill:
            # Speculative windows process synchronously (the prompt-lookup
            # drafter needs the host-fetched history before it can propose
            # the next span), so there is never an in-flight deque for
            # deferred first tokens to ride — the combination would leave
            # carried-ids scatters that are fetched nowhere.
            raise ValueError(
                'draft_k and defer_prefill are mutually exclusive: '
                'speculative windows fetch every window synchronously '
                '(the drafter needs host-side history), which removes '
                "defer_prefill's in-flight deque (docs/speculative.md)"
            )
        if self.host_kv_tier_bytes and not self.enable_prefix_cache:
            raise ValueError(
                'host_kv_tier_bytes needs enable_prefix_cache: the tier '
                'spills and promotes PREFIX-CACHE blocks — without the '
                'cache nothing ever reaches it (docs/prefix_caching.md)'
            )
        if self.disk_kv_tier_dir and not self.host_kv_tier_bytes:
            raise ValueError(
                'disk_kv_tier_dir needs host_kv_tier_bytes > 0: spills '
                'reach disk by writing through the host tier, and '
                'promotions route disk → host → device '
                '(docs/prefix_caching.md "Tier hierarchy")'
            )
        if self.peer_kv_endpoints is not None and not self.host_kv_tier_bytes:
            raise ValueError(
                'peer_kv_endpoints needs host_kv_tier_bytes > 0: peer '
                'fetches land in the host pool and promote host → device '
                'exactly like a disk hit (docs/routing.md "Peer KV tier")'
            )
        if self.peer_kv_serve_endpoint and not self.host_kv_tier_bytes:
            raise ValueError(
                'peer_kv_serve_endpoint needs host_kv_tier_bytes > 0: the '
                'KVBlockServer answers HAS/GET from the host/disk pools — '
                'without a host tier there is nothing to serve '
                '(docs/routing.md "Peer KV tier")'
            )
        if self.admission_control and self.ttft_slo_s <= 0:
            raise ValueError(
                'admission_control needs ttft_slo_s > 0: shedding is '
                'defined as refusing load whose predicted TTFT busts the '
                'SLO — without an SLO there is no shed threshold '
                '(docs/resilience.md "Shedding policy")'
            )
        return self
    # Automatic prefix caching (docs/prefix_caching.md): full prompt
    # blocks enter a hash-chain cache as they prefill; later requests
    # sharing a block-aligned prefix reuse those KV blocks (refcounted,
    # LRU-evicted under pool pressure) and prefill ONLY the uncached tail
    # — TTFT and prefill compute drop from O(prompt) to O(tail) for
    # prefix-heavy workloads (RAG system prompts, MCQA stems).
    enable_prefix_cache: bool = False
    # Host-RAM KV tier behind the prefix cache (docs/prefix_caching.md
    # "Tier hierarchy"): evicted ref==0 cache blocks spill device→host
    # into a bounded digest-keyed pool instead of dropping their KV, and
    # later same-prefix arrivals promote them back into the paged pool
    # via async jax.device_put overlapped with in-flight decode windows
    # — warm TTFT at prefix working sets far beyond HBM. Byte budget of
    # the host pool (LRU); 0 disables the tier (HBM-only cache, the
    # pre-tier behavior). Requires enable_prefix_cache.
    host_kv_tier_bytes: int = 0
    # Optional disk tier under the host pool: spills write THROUGH to
    # one digest-named file per block in this directory, so a fresh
    # engine serving the same corpus promotes straight from a previous
    # process's spills (cold-start warm TTFT). None disables.
    disk_kv_tier_dir: str | None = None
    # Disk-tier byte budget (LRU; evictions there are final drops).
    disk_kv_tier_bytes: int = 1 << 30
    # Peer KV tier (docs/routing.md "Peer KV tier"): sibling replicas'
    # KVBlockServer endpoints ('tcp://host:port') to consult AFTER host
    # and disk miss — a replica adopts a peer's spilled .kvblock payloads
    # through the same async promotion path as a disk hit, the
    # content-addressed KV-handoff seed of prefill/decode disaggregation.
    # None disables the tier entirely; an empty tuple enables it with no
    # peers yet (endpoints can be added at runtime via
    # engine.kv_tier.peer.add_endpoint). Requires host_kv_tier_bytes > 0.
    peer_kv_endpoints: tuple[str, ...] | None = None
    # Serve THIS replica's spilled blocks to peers: a zmq bind spec for
    # the KVBlockServer ('tcp://127.0.0.1:0' picks a free port; the
    # resolved endpoint is exposed as engine.peer_kv_endpoint). None
    # disables serving. Requires host_kv_tier_bytes > 0.
    peer_kv_serve_endpoint: str | None = None
    # Per-request timeout for one peer HAS/GET round trip, and the
    # cool-off a failing endpoint sits out before being consulted again
    # (fetch failure degrades to cold prefill, never blocks serving).
    peer_kv_timeout_ms: int = 500
    peer_kv_backoff_s: float = 5.0
    # Split uncached prefill tails longer than this many tokens into
    # bucketed chunks dispatched sequentially (each chunk attends to the
    # KV already in the paged cache), so one long prompt cannot
    # monopolize the chip in a single monolithic dispatch. 0 disables
    # chunking.
    prefill_chunk_tokens: int = 0
    # TTFT service-level objective in seconds (0 = no SLO accounting).
    # When set, every finished request counts into
    # distllm_request_slo_total{outcome=met|missed} and met requests'
    # output tokens into distllm_engine_goodput_tokens_total — goodput,
    # the throughput a latency-bound deployment actually delivered.
    ttft_slo_s: float = 0.0
    # --- resilience (docs/resilience.md) ---
    # Per-request wall-clock deadline (enqueue → terminal state), in
    # seconds; 0 disables. A request that outlives it — stuck behind a
    # stalled window, a livelocked retry ladder, or simply abandoned —
    # finishes with finish_reason='timeout' and FREES its KV blocks
    # instead of holding pool capacity forever. The chat server defaults
    # this on (ChatAppConfig.build_generator).
    request_deadline_s: float = 0.0
    # Crash-domain recovery: how many times a request's dispatches may
    # fail before it is quarantined to the terminal FAILED status with a
    # recorded error. 0 (default) preserves the legacy contract — the
    # first dispatch exception propagates to the caller; > 0 makes the
    # serving loop roll per-row state back, back off
    # (retry_backoff_s * 2^attempt, capped), and retry the window, so
    # one poison request or transient backend fault cannot take the
    # whole batch down with it.
    max_dispatch_retries: int = 0
    # Base of the bounded exponential backoff between window retries.
    retry_backoff_s: float = 0.05
    # SLO-aware admission control (requires ttft_slo_s > 0): predict
    # TTFT at enqueue from EWMA-measured prefill/window rates (roofline
    # floors before traffic) and the current backlog, and REFUSE —
    # raise resilience.EngineOverloaded with an honest Retry-After —
    # requests whose prediction busts the SLO, instead of queueing them
    # into guaranteed misses. Runtime-flippable via
    # ``engine.admission_control`` (the attribution pattern).
    admission_control: bool = False
    # Decode windows in flight during generate_ids (2 hides the
    # host<->device round trip behind the next window's compute).
    pipeline_depth: int = 2
    # OPT-IN, off by default. Keeps prefill's first-token fetch on device
    # and processes it with the in-flight window records (sampled tokens
    # scatter into the carried last-ids vector). Token-exact either way.
    # It trades blocking sample fetches for extra tiny dispatches
    # (scatter/merge/slices); which side wins on the chip is not measured
    # on today's code. enable_mixed_batching answers the same
    # prefill-serialization gap and the validator rejects enabling both.
    defer_prefill: bool = False
    # Mixed prefill+decode serving windows (docs/serving.md): each fused
    # decode dispatch may also carry up to max_window_prefill_tokens of
    # uncached prefill-tail chunk tokens, so prefill work rides the
    # weight stream (and the dispatch) the decode window already pays for
    # instead of serializing between windows (never measured on the
    # chip). Token-identical to the separate-prefill path
    # under greedy sampling (tested); stochastic sampling draws from a
    # different key-split order.
    enable_mixed_batching: bool = False
    # Budget of prefill-chunk tokens one mixed window may carry (the
    # max_num_batched_tokens analogue for the ridden prefill share).
    # Chunk spans additionally respect prefill_chunk_tokens when set, so
    # chunk planning composes with the PR-2 chunked-prefill buckets.
    max_window_prefill_tokens: int = 256
    # Prefill-chunk ROWS (distinct requests) per mixed window. Each
    # (rows, bucket) pair is a compiled window shape; keep this small —
    # on TPU every extra mixed shape is another multi-minute unrolled-
    # window compile at warmup (see docs/serving.md).
    max_window_prefill_seqs: int = 2
    # Prompt-lookup speculative decoding (docs/speculative.md): up to
    # draft_k tokens per row are proposed from the row's OWN prompt+output
    # history and verified in ONE ragged dispatch (per-row spans of
    # 1 + draft_k through the same write-then-attend kernel as paged
    # prefill), so every accepted draft token is a decode token that
    # skipped its weight pass. Greedy output with speculation on is
    # token-identical to speculation off (tested across the full engine
    # identity matrix); rows with temperature > 0 draft too and are
    # verified device-side by exact rejection sampling against the
    # filtered target distribution (docs/speculative.md "Sampled
    # verification") — their sampled streams stay deterministic per
    # (seed, schedule) via counter-based per-row PRNG keys.
    # 0 disables speculation entirely (the classic decode-scan windows).
    # Speculative windows process synchronously (the drafter needs the
    # host-fetched history), so pipeline_depth is effectively 1 while
    # draft_k > 0: the trade is dispatch-latency hiding for weight-pass
    # skipping, which wins at the low-batch/low-latency end where decode
    # is weight-stream-bound.
    draft_k: int = 0
    # n-gram length the prompt-lookup drafter matches on. Longer n-grams
    # propose less often but more precisely.
    spec_ngram: int = 2
    # Where drafts come from. 'prompt_lookup' is the real drafter;
    # 'none' proposes nothing — every window is a span-1 verify dispatch
    # through the SAME compiled executable, which makes it the
    # bit-identity baseline for speculation A/Bs in bf16: two compiled
    # programs (the decode scan vs the ragged verify) may round a
    # near-tied logit differently, so cross-KERNEL token identity is
    # only guaranteed in fp32, while drafting-on vs drafting-off inside
    # the verify kernel is bit-identical in any dtype
    # (docs/speculative.md; tests/test_spec.py asserts it).
    spec_draft_source: str = 'prompt_lookup'
    # Serving-path attribution (docs/observability.md): per-window
    # host/put/dispatch/fetch timing split on flight records,
    # jax.profiler.TraceAnnotation labels on every dispatch kind, and the
    # analytic roofline gauges (distllm_engine_mfu /
    # distllm_engine_bandwidth_utilization). Pure host-side bookkeeping —
    # token output is bit-identical on vs off (tests/test_loadgen.py
    # asserts it). Off sheds the record fields, profiler annotations, and
    # roofline math; the step spans' time.monotonic() reads stay
    # (nanoseconds — gating them would complicate every window path for
    # nothing measurable).
    attribution: bool = True
    # Metric-history sampler (docs/observability.md "Metric history &
    # sampling"): > 0 makes THIS engine own a background
    # ``HistorySampler`` ticking the process-wide ``MetricsHistory`` at
    # the given interval, started in ``__init__`` and stopped in
    # ``shutdown()`` (no leaked thread — tested). 0 (default) starts
    # nothing: the chat server owns the process sampler in serving
    # deployments, and two samplers over one history would double the
    # sample density for no information. Set it only for headless /
    # scripted engines that want history without a server.
    history_interval_s: float = 0.0
    seed: int = 0

    @field_validator('spec_draft_source')
    @classmethod
    def _known_draft_source(cls, v: str) -> str:
        if v not in ('prompt_lookup', 'none'):
            raise ValueError(
                "spec_draft_source must be 'prompt_lookup' or 'none'"
            )
        return v

    @field_validator('attn_backend')
    @classmethod
    def _known_attn_backend(cls, v: str) -> str:
        from distllm_tpu.ops.paged_attention import ATTN_BACKENDS

        if v not in ATTN_BACKENDS:
            raise ValueError(
                f'attn_backend must be one of {ATTN_BACKENDS}, got {v!r}'
            )
        return v

    @field_validator('kv_cache_dtype')
    @classmethod
    def _known_kv_cache_dtype(cls, v: str) -> str:
        if v not in ('auto', 'bf16', 'fp32', 'int8'):
            raise ValueError(
                "kv_cache_dtype must be 'auto', 'bf16', 'fp32', or "
                f"'int8', got {v!r}"
            )
        return v


def bounce_slabs(rows: int, nbytes: int) -> tuple[int, list[int]]:
    """How a leaf of ``rows`` indices along dim 0 and ``nbytes`` bytes goes
    through the host in ``_migrate_params``: ``(slab, starts)``, ``slab``
    indices a transfer from each of ``starts``. One index a transfer where
    an index is a MiB or more (a layer of a stacked kernel); else slabs of
    about 64 MiB, all of one size: the last starts at ``rows - slab``."""
    row_bytes = max(1, nbytes // rows)
    if row_bytes >= (1 << 20):
        return 1, list(range(rows))
    slab = max(1, min(rows, (64 << 20) // row_bytes))
    return slab, sorted({min(i, rows - slab) for i in range(0, rows, slab)})


def auto_layout_formats(params):
    """What the decode window's AOT compile asks for, leaf by leaf of the
    weights: ``Layout.AUTO``, but the device's default layout for a leaf
    whose minor dimension does not fill a lane tile (under 128). Such a
    leaf has nothing to gain from a layout of the window's own, and the
    one the window picks for it is not one every other program runs: for a
    ``bf16[2, 256, 12]`` gate kernel the device's default is ``(2, 0, 1)``
    in ``T(2,128)`` tiles (12288 bytes) and the window chose ``(0, 2, 1)``
    in ``T(8,128)`` (16384); the prefill program, jitted over the migrated
    tree, then failed at its first dispatch (``expected parameter 6 of
    size 12288 ... got 16384``; on the chip, PR 30). Kept at the default,
    the leaf is the same buffer to the window and to every other program,
    and migration leaves it where it is."""
    from jax.experimental.layout import Format, Layout

    return jax.tree.map(
        lambda x: Format()
        if x.shape and x.shape[-1] < 128 else Format(Layout.AUTO),
        params,
    )


class LLMEngine:
    """Drives a Mistral-family decoder with paged KV + continuous batching.

    ``model_cfg`` may be a :class:`~distllm_tpu.models.mixtral.
    MixtralConfig` too: the shared serving machinery dispatches the MLP
    block on pytree structure (``models/mistral.py _mlp_block``), so
    dense SwiGLU and MoE families serve through one engine — mirroring
    the reference, whose vLLM backend serves both.

    What a sequence holds is the model's to say, in one description
    (``model_cfg.cache_spec()``, ``models.common.CacheSpec``), and the
    pools, tables, programs and refusals are built from it (docs/serving.md
    "Cache groups"). A ``state`` (``models/granite_hybrid.py``: recurrent
    layers between attention layers) makes a HYBRID: its sequences hold KV
    pages for the paged layers only and one slot of a ``StatePool`` beside
    them. A windowed paged group (``models/laguna.py``) gets a pool, a
    table and an allocator of its own (``kv_cache.WindowBlocks``), and its
    sequences hold only the blocks a query still sees. What cannot be right
    yet with either (prefix cache, KV tiers, mixed and speculative windows,
    an int8 pool, a mesh) is refused at construction, by name.
    """

    def __init__(
        self,
        model_cfg: 'mistral.MistralConfig | object',
        params: dict,
        tokenizer,
        config: EngineConfig | None = None,
        mesh=None,
        own_params: bool = False,
    ) -> None:
        """``own_params=True`` hands the engine ownership of ``params``:
        destructive HBM optimizations (weight relayout, quantized-source
        deletion) may delete the caller's buffers. Required to serve 7B
        bf16 on a 16 GB chip — without it the engine keeps the caller's
        copies alive and falls back to layout-copying dispatches."""
        self.config = config or EngineConfig()
        # Startup attribution (docs/observability.md): every expensive
        # init/warmup phase lands as a 'compile' flight record, so a
        # wedged startup — the r03/r04 bench failure mode — names the
        # phase it died in.
        self._compile_watcher = get_compile_watcher().listen()
        # Per-engine dedup scope: a rebuilt engine's jit wrappers really
        # recompile, so its phases must start cold in the watcher.
        self._compile_scope = self._compile_watcher.new_scope()
        # The whole construction is one phase around its inner ones: what
        # it spends under none of them (the scheduler and its native build,
        # program construction, pricing) is that phase's remainder.
        family = type(model_cfg).__module__.rpartition('.')[2]
        with self._compile_watcher.phase(
            'engine_init', f'{family}:b{self.config.max_num_seqs}',
            scope=self._compile_scope,
        ):
            self._build(model_cfg, params, tokenizer, mesh, own_params)

    def _build(self, model_cfg, params, tokenizer, mesh, own_params) -> None:
        self.model_cfg = model_cfg
        self.params = params
        self.tokenizer = tokenizer
        self._own_params = own_params
        cfg = self.config
        # The first engine in a process also pays (and attributes) the
        # real backend init here; later calls are near-instant cache-hit
        # records.
        record_backend_init(self._compile_watcher)

        # Tensor parallelism: K/V pages shard over the kv heads on the
        # mesh's model axis (same split as the attention heads in
        # param_specs; dim 3 of the pool is a token's ``N_kv * Hd`` row,
        # whole heads in contiguous runs, so each shard holds whole heads),
        # so paged gather/scatter stays local per shard;
        # host-built step inputs (ids / positions / block tables) are
        # replicated explicitly — committed single-device arrays would
        # conflict with mesh-sharded params inside the jitted step.
        kv_sharding = None
        self._replicated = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if model_cfg.num_kv_heads % mesh.shape.get('model', 1):
                raise ValueError(
                    f'num_kv_heads={model_cfg.num_kv_heads} not divisible '
                    f"by tensor parallel degree {mesh.shape.get('model', 1)}"
                )
            kv_sharding = NamedSharding(mesh, P(None, None, None, 'model'))
            self._replicated = NamedSharding(mesh, P())

        # Resolve the KV storage dtype ONCE (the attn/qmm pinning
        # pattern): 'auto' stores the model compute dtype — bit-exact
        # with the pre-kv_cache_dtype engine; 'int8' switches the pool to
        # QuantizedKV storage (docs/serving.md "Quantized KV cache").
        kv_pool_dtype = {
            'bf16': 'bfloat16', 'fp32': 'float32', 'int8': 'int8',
        }.get(cfg.kv_cache_dtype, model_cfg.dtype)

        # What a sequence holds, asked of the model's config in one
        # description (``models.common.CacheSpec``): the paged groups, the
        # first of them the scheduler's; a fixed state tree, or none; the
        # module whose programs serve it. Pools, tables, programs and
        # refusals below come from that, never from the family's name.
        # The pools are allocated after the weight migration.
        spec = model_cfg.cache_spec()
        self.cache_spec = spec
        self._refuse_unservable(spec, cfg, mesh, kv_pool_dtype)
        # Positions a sequence decides together (1: a token a forward), and
        # the denoise forwards a block of them takes.
        self._block = spec.block
        self._denoise_steps = cfg.denoise_steps or spec.block
        self._programs = importlib.import_module(spec.programs)
        # A family may hold some leaves otherwise for its programs than its
        # public tree does (``deepseek_v3.serving_params``: a layer an
        # array where a stack's slices would be written out every step);
        # everything below takes the tree leaf by leaf. Without the
        # function the tree is served as it was given.
        serving_params = getattr(self._programs, 'serving_params', None)
        if serving_params is not None:
            self.params = serving_params(self.params, own=own_params)
        # On the prefill and decode records of a model whose stack runs
        # several times a token: the passes, and the K/V planes they fill.
        self._loop_fields = {} if spec.passes == 1 else {
            'loop_passes': spec.passes, 'kv_planes': spec.paged[0].num_layers,
        }
        self.state_pool = None
        if spec.state is not None:
            self.state_pool = StatePool(
                spec.state, cfg.max_num_seqs, lazy=True
            )

        # Lazy: the pool is materialized only after the (transient-heavy)
        # weight-layout migration below, so migration headroom isn't
        # squeezed by an idle 1-6 GiB of zeros.
        def pool(group, num_blocks):
            return PagedKVCache.for_group(
                group, model_cfg, num_blocks, cfg.block_size,
                dtype=kv_pool_dtype, sharding=kv_sharding, lazy=True,
            )

        self.kv = pool(spec.paged[0], cfg.num_blocks)
        self.max_blocks_per_seq = self.kv.blocks_needed(cfg.max_model_len)
        self.prefill_buckets = bucket_ladder(
            cfg.max_model_len, cfg.prefill_min_bucket, scheme='pow2'
        )
        # A windowed group has a pool and a table of its own; who holds
        # which of its blocks is ``WindowBlocks``' (kv_cache.py), beside
        # the scheduler that owns the first group's.
        self.window_kv = None
        self.window_blocks = None
        if spec.windowed:
            self._build_window_group(spec.windowed[0], pool)

        # All admission / preemption / block-budget decisions live in the
        # scheduler (native C++ core, Python twin fallback); the wrapper
        # publishes queue depth / occupancy / admit-defer-preempt metrics.
        self.sched = InstrumentedScheduler(
            make_scheduler(
                cfg.num_blocks,
                cfg.block_size,
                cfg.max_num_seqs,
                prefer_native=cfg.prefer_native_allocator,
            ),
            num_blocks=cfg.num_blocks,
        )
        self._requests: dict[int, Request] = {}
        self._next_id = itertools.count()
        # Step counter: the ``seq`` of every step span (flight records and
        # distllm: annotations alike, docs/observability.md).
        self._step_seq = itertools.count()
        self._finished: dict[int, Request] = {}
        # Serving-loop counters (windows, prefill dispatches, EOS-overshoot
        # waste); generate_ids folds them into ``telemetry`` per run so the
        # bench JSON carries the steady-state split (VERDICT r2 weak #6/#10).
        from collections import Counter

        self._stats: 'Counter[str]' = Counter()
        # Flight recorder: one bounded ring record per prefill dispatch /
        # decode window / finished request. The process-wide ring also
        # feeds the StallWatchdog's default progress signal, so a wedged
        # engine is detectable without any extra wiring.
        self.flight = get_flight_recorder()
        # Resilience layer (docs/resilience.md): the process fault
        # injector (inert unless a chaos schedule armed it), per-request
        # consecutive dispatch-failure counts feeding the quarantine
        # threshold, prefill dispatches that must re-run after a failed
        # attempt, and the recovery backoff state.
        self._faults = get_fault_injector()
        self._dispatch_failures: dict[int, int] = {}
        self._pending_prefill: list[int] = []
        self._consecutive_failures = 0
        # SLO-aware admission control (runtime-flippable, the
        # attribution pattern) + the EWMA-measured predictor inputs
        # (_record_step feeds them; roofline floors cover cold start).
        self.admission_control = cfg.admission_control
        self._ewma: dict[str, float] = {}
        # Metric-history sampler, engine-owned ONLY when configured
        # (history_interval_s > 0); serving deployments leave this 0 and
        # let the chat server own the process sampler. Stopped (and the
        # thread joined) in shutdown() — never leaks past the engine.
        self._history_sampler = None
        if cfg.history_interval_s > 0:
            from distllm_tpu.observability.history import (
                HistorySampler,
                get_metrics_history,
            )

            self._history_sampler = HistorySampler(
                get_metrics_history(), interval_s=cfg.history_interval_s
            )
            self._history_sampler.start()

        model = self.model_cfg

        if cfg.quantization:
            # Weight-only quantized serving (reference: bnb NF4 in the HF
            # generator, huggingface_backend.py:66-77): codes live in HBM;
            # dequant happens INSIDE the compiled step, per layer, at the
            # point of use (common.dense unpacks QTensor leaves riding the
            # layer scan) — never as a whole-tree pass, which would
            # materialize the full float model as HLO temps.
            from distllm_tpu.ops.quantization import quantize_pytree

            # ``delete_source`` streams the conversion when we own the
            # buffers: each replaced bf16 leaf is freed BEFORE its codes are
            # materialized, so HBM peaks at the unquantized weights instead
            # of weights+codes (which OOMed a 16 GiB v5e at 7B dims).
            with self._compile_watcher.phase(
                'quantize', cfg.quantization, compiles=False,
                scope=self._compile_scope,
            ):
                self.params = quantize_pytree(
                    self.params,
                    mode=cfg.quantization,
                    out_dtype=model.dtype,
                    delete_source=self._own_params,
                )
            # Resolve the quantized-matmul tier ONCE, here, and pin it
            # into the model config the jitted forwards close over.
            # dense() otherwise re-reads the process-global
            # default_backend() at trace time, so a set_default_backend
            # call between engine construction and the first dispatch
            # could route a 'pallas' kernel under a TP mesh — past the
            # mesh check below (the TP-mesh/pallas bypass, ADVICE r5).
            from distllm_tpu.ops import quantized_matmul as _qmm

            resolved_qmm = (
                getattr(model, 'qmm_backend', None) or _qmm.default_backend()
            )
            if mesh is not None and resolved_qmm in ('pallas', 'interpret'):
                # GSPMD cannot partition a pallas_call over model-sharded
                # int8 kernels; the XLA scale-after-dot tier partitions
                # like any dot. 'auto' already means 'xla', so only an
                # explicit 'pallas' pin needs rejecting.
                raise ValueError(
                    'quantized-matmul backend '
                    f'{resolved_qmm!r} cannot serve under a '
                    "tensor-parallel mesh; use 'auto'/'xla'"
                )
            if hasattr(model, 'model_copy'):
                model = model.model_copy(update={'qmm_backend': resolved_qmm})
                self.model_cfg = model

        def prefill_fn(params, ids, mask, last_pos):
            hidden, k, v = mistral.prefill(params, model, ids, mask)
            # Only the last valid position's logits are sampled; computing
            # the lm_head for [B, S, V] would waste MXU time and HBM.
            last_hidden = jnp.take_along_axis(
                hidden, last_pos[:, None, None], axis=1
            )
            # K and V leave as the rows the pool stores: folded here, the
            # scatter program behind this one (``_write_prefill``, lowered
            # again for each commitment of the pools) has no relayout of
            # its updates to compile.
            return (
                mistral.logits(params, model, last_hidden)[:, 0],
                fold_heads(k), fold_heads(v),
            )

        self._prefill = jax.jit(prefill_fn)

        # Resolve the paged-attention backend ONCE, here, and close every
        # jitted serving function below over the result — the qmm_backend
        # pinning pattern (ops.paged_attention.resolve_attn_backend):
        # 'auto' picks the fused ragged Pallas kernel on TPU for
        # CI-covered head dims and the always-available XLA baseline
        # everywhere else, and a config change after construction can
        # never re-route live dispatches.
        from distllm_tpu.ops.paged_attention import (
            kv_sublane_tile,
            resolve_attn_backend,
        )

        attn_backend = resolve_attn_backend(
            cfg.attn_backend, model,
            # 'auto' eligibility includes the kernel's DMA contract on the
            # KV block geometry — a config the kernel would reject must
            # resolve to XLA, never trace into a ValueError. The STORAGE
            # dtype decides the sublane tile: an int8 pool needs
            # block_size % 32 == 0, so int8 + the default block_size=16
            # quietly keeps the XLA tier under 'auto', as does an int8
            # pool under 64-wide heads at any block size.
            block_size=cfg.block_size, kv_dtype=kv_pool_dtype,
        )
        _sublane = kv_sublane_tile(kv_pool_dtype)
        if (
            jnp.dtype(kv_pool_dtype) == jnp.dtype(jnp.int8)
            and attn_backend in ('pallas', 'interpret')
            and cfg.block_size % _sublane
        ):
            # Explicit kernel pin on an ineligible int8 KV geometry: fail
            # at construction with the fix, not mid-warmup from the
            # kernel's trace-time guard (the head-dim guard's discipline).
            # Full-precision pools keep their seed behavior — interpret
            # mode runs any block size, and 'auto' already routes
            # compiled-TPU ineligibility to XLA via resolve_attn_backend.
            raise ValueError(
                f'attn_backend={attn_backend!r} needs block_size % '
                f'{_sublane} == 0 for {jnp.dtype(kv_pool_dtype).name} KV '
                f'caches, got block_size={cfg.block_size}; use '
                f'block_size={_sublane} (EngineConfig.block_size) or '
                "attn_backend='xla'"
            )
        if mesh is not None and attn_backend != 'xla':
            # GSPMD cannot partition the ragged pallas_call over the
            # kv-head-sharded cache planes (the qmm 'pallas' TP rule,
            # applied to attention). 'auto' quietly keeps the XLA tier —
            # it partitions like any gather/dot — while an explicit pin
            # must fail loudly rather than serve a broken partitioning.
            if cfg.attn_backend == 'auto':
                attn_backend = 'xla'
            else:
                raise ValueError(
                    f'attn_backend {attn_backend!r} cannot serve under a '
                    "tensor-parallel mesh; use 'auto'/'xla'"
                )
        if (
            cfg.attn_backend == 'auto'
            and attn_backend == 'xla'
            and jax.default_backend() == 'tpu'
        ):
            # The fallback is correct but silently costs ~3x decode —
            # this is the ONE site that sees every reason 'auto' can
            # land on XLA (head dim, KV block geometry, TP mesh), so the
            # warning lives here; telemetry carries the resolved value.
            import logging

            logging.getLogger(__name__).warning(
                "attn_backend='auto' resolved to the XLA paged-attention "
                'path on a TPU (head_dim %d, block_size %d, kv dtype %s, '
                'tensor parallel: %s) — the fused Pallas kernel is not '
                'eligible for this config',
                model.head_size, cfg.block_size,
                jnp.dtype(kv_pool_dtype).name, mesh is not None,
            )

        # Automatic prefix caching: hash-chain over full prompt blocks,
        # refcounted sharing, LRU eviction (docs/prefix_caching.md).
        # Cache-hit tails and chunked prefills dispatch through
        # prefill_paged (write tail K/V, attend over the paged cache).
        self.prefix_cache = (
            PrefixCache(cfg.block_size) if cfg.enable_prefix_cache else None
        )
        # Host-RAM (and disk, and peer) KV tier behind the prefix cache
        # (docs/prefix_caching.md "Tier hierarchy"): eviction pressure
        # cascades HBM → host → disk → peer → drop, and host/disk/peer
        # hits promote back into the paged pool via async device_put at
        # admission. The peer hop (docs/routing.md) consults sibling
        # replicas' KVBlockServers after a local miss; this replica's own
        # spills are served back when peer_kv_serve_endpoint is set.
        self.kv_tier = None
        self.peer_kv_endpoint: str | None = None
        self._peer_kv_server = None
        if cfg.host_kv_tier_bytes:
            disk = (
                DiskKVTier(cfg.disk_kv_tier_dir, cfg.disk_kv_tier_bytes)
                if cfg.disk_kv_tier_dir
                else None
            )
            peer = (
                PeerKVTier(
                    cfg.peer_kv_endpoints,
                    timeout_ms=cfg.peer_kv_timeout_ms,
                    failure_backoff_s=cfg.peer_kv_backoff_s,
                )
                if cfg.peer_kv_endpoints is not None
                else None
            )
            self.kv_tier = HostKVTier(
                cfg.host_kv_tier_bytes, disk=disk, peer=peer
            )
            if cfg.peer_kv_serve_endpoint:
                from distllm_tpu.parallel.fabric import KVBlockServer

                self._peer_kv_server = KVBlockServer(
                    self.kv_tier.contains_local,
                    self.kv_tier.encoded_local,
                    bind=cfg.peer_kv_serve_endpoint,
                ).start()
                self.peer_kv_endpoint = self._peer_kv_server.endpoint
        # In-flight promotions: rid -> completion record ({'token': a tiny
        # post-scatter device slice whose readiness proves the promoted
        # KV landed, timing fields}). The request stays non-decode-ready
        # (prefill_target gate) until _finish_promotions retires it.
        self._promoting: dict[int, dict] = {}
        # Promotion overlap accounting (tier_summary): span = begin →
        # retire wall time, wait = the blocking part of that span (the
        # one audited completion sync). overlap = 1 - wait/span.
        self._tier_times = {'promote_wait_s': 0.0, 'promote_span_s': 0.0}
        # Spill fetch (device→host gather of evicted blocks' KV) and
        # promotion write-back (scatter of device_put'ed host KV).
        # Block-count dims pad up a pow2 ladder so the jit cache stays
        # O(log max_blocks_per_seq); pad slots index the trash block.
        # tree.map keeps these pool-container-generic: for a bare-array
        # pool the maps ARE the direct ops (bit-identical HLO); for a
        # QuantizedKV pool the int8 data and the fp32 scales both carry
        # their block axis at axis 1, so one lambda moves both planes —
        # spills and promotions transport quantized blocks natively,
        # never through a dequantized copy.
        self._gather_blocks = jax.jit(_gather_blocks_all_layers)
        self._write_promoted = jax.jit(
            lambda k, v, kp, vp, idx: jax.tree.map(
                lambda c, p: c.at[:, idx].set(p.astype(c.dtype)),
                (k, v), (kp, vp),
            ),
            donate_argnums=(0, 1),
        )
        # Tiny post-scatter slice whose readiness proves the promoted KV
        # landed — the promotion-landed probe.
        self._probe = jax.jit(
            lambda a: jnp.ravel(jax.tree.leaves(a)[0])[:1]
        )
        _max_tables = cfg.max_model_len

        # The family's two serving programs (``spec.programs``), under the
        # names the family's traces and metrics know them by. ``extra`` is
        # a hybrid's (state pool, slots); with several paged groups ``k``,
        # ``v`` and ``bt`` are tuples, one entry a group.
        programs, prefix = self._programs, spec.program_prefix
        donate_state = spec.state is not None

        def prefill_paged_fn(params, ids, pos, k, v, bt, ctx, tails, *extra):
            return programs.prefill_paged(
                params, model, ids, pos, k, v, bt, ctx, tails, *extra,
                max_table_positions=_max_tables, attn_backend=attn_backend,
            )

        if prefix:
            prefill_paged_fn.__name__ = f'{prefix}prefill_fn'
        self._prefill_paged = jax.jit(
            prefill_paged_fn,
            donate_argnums=(3, 4, 8) if donate_state else (3, 4),
        )
        if not spec.dense_prefill:
            # Every prefill takes the paged route: one family of programs
            # carries what a sequence holds from span to span.
            self._prefill = None
        # Batched COW: copy shared blocks' K/V (all layers) into the
        # requests' private copies in one dispatch. tree.map for the
        # same reason as the tier jits above: a quantized source block's
        # int8 data AND its scale row copy together, so the private copy
        # stays bit-exact (no requantization on COW).
        self._cow_copy = jax.jit(
            lambda k, v, src, dst: jax.tree.map(
                lambda c: c.at[:, dst].set(c[:, src]), (k, v)
            ),
            donate_argnums=(0, 1),
        )

        num_steps = cfg.decode_steps
        max_tables = cfg.max_model_len

        def window_fn(
            params, ids, pos, ctx, k, v, bt, steps_left, temp, top_p, min_p,
            top_k, seeds, *state,
        ):
            return programs.decode_loop(
                params, model, ids, pos, k, v, bt, ctx, steps_left,
                temp, top_p, min_p, top_k, seeds, num_steps=num_steps,
                attn_backend=attn_backend, max_table_positions=max_tables,
                sampling_top_window=cfg.sampling_top_window,
                **window_extra(state),
            )

        def window_extra(state):
            # A block model's last operand is its rows' unmask thresholds,
            # its denoise steps a static; a hybrid's its state pool.
            if spec.block > 1:
                return {
                    'unmask_threshold': state[0],
                    'denoise_steps': self._denoise_steps,
                }
            return {'state': state[0]} if state else {}

        window_fn.__name__ = f'{prefix}window_fn'
        # The pools a window updates in place: K, V and a hybrid's state.
        self._window_donate = (4, 5, 13) if donate_state else (4, 5)
        self._decode_window = jax.jit(
            window_fn, donate_argnums=self._window_donate
        )

        # Mixed serving windows: chunk rows + the decode scan in ONE
        # dispatch (mistral.mixed_window; docs/serving.md). Built only
        # when enabled — the shapes are extra compiles a pure-decode
        # deployment never wants.
        def mixed_fn(
            params, ids, pos, ctx, k, v, bt, steps_left, temp, top_p,
            min_p, top_k, seeds, c_ids, c_pos, c_bt, c_ctx, c_tails,
            c_temp, c_top_p, c_min_p, c_top_k, c_seeds,
        ):
            return mistral.mixed_window(
                params, model, ids, pos, k, v, bt, ctx, steps_left,
                temp, top_p, min_p, top_k, seeds, c_ids, c_pos, c_bt,
                c_ctx, c_tails, c_temp, c_top_p, c_min_p, c_top_k,
                c_seeds, num_steps=num_steps,
                attn_backend=attn_backend, max_table_positions=max_tables,
                sampling_top_window=cfg.sampling_top_window,
            )

        self._mixed_fn = mixed_fn
        self._mixed_window = (
            jax.jit(mixed_fn, donate_argnums=(4, 5))
            if cfg.enable_mixed_batching
            else None
        )

        # Speculative verify windows (docs/speculative.md): one ragged
        # dispatch scores every row's [last_token, drafts...] span. Two
        # variants — plain, and chunk-carrying (mixed batching): the
        # chunk tuple is pytree-static, so each compiles its own graph
        # and a pure-spec deployment never compiles the chunk shapes.
        def spec_fn(
            params, ids, pos, ctx, k, v, bt, tails, temp, top_p, min_p,
            top_k, seeds,
        ):
            return mistral.spec_window(
                params, model, ids, pos, k, v, bt, ctx, tails,
                temp, top_p, min_p, top_k, seeds,
                max_table_positions=max_tables,
                sampling_top_window=cfg.sampling_top_window,
                attn_backend=attn_backend,
            )

        def spec_mixed_fn(
            params, ids, pos, ctx, k, v, bt, tails, temp, top_p, min_p,
            top_k, seeds, c_ids, c_pos, c_bt, c_ctx, c_tails, c_temp,
            c_top_p, c_min_p, c_top_k, c_seeds,
        ):
            return mistral.spec_window(
                params, model, ids, pos, k, v, bt, ctx, tails,
                temp, top_p, min_p, top_k, seeds,
                chunk=(
                    c_ids, c_pos, c_bt, c_ctx, c_tails, c_temp, c_top_p,
                    c_min_p, c_top_k, c_seeds,
                ),
                max_table_positions=max_tables,
                sampling_top_window=cfg.sampling_top_window,
                attn_backend=attn_backend,
            )

        self._spec_fn = spec_fn
        self._spec_mixed_fn = spec_mixed_fn
        self._spec_window = (
            jax.jit(spec_fn, donate_argnums=(4, 5)) if cfg.draft_k else None
        )
        self._spec_mixed_window = (
            jax.jit(spec_mixed_fn, donate_argnums=(4, 5))
            if cfg.draft_k and cfg.enable_mixed_batching
            else None
        )
        # Resolved-at-serve-time values: a config that believes it enabled
        # the Pallas kernel can otherwise ship 3x slower with no signal.
        # (attn_backend here is the RESOLVED selector, never 'auto'.)
        self.telemetry: dict[str, str] = {'attn_backend': attn_backend}
        # Scrape-visible twin of the telemetry field: exactly one backend
        # label reads 1.
        for _be in _metrics.ATTN_BACKEND_LABELS:
            _metrics.ATTN_BACKEND_INFO.labels(backend=_be).set(
                1.0 if _be == attn_backend else 0.0
            )
        # Same pattern for the RESOLVED KV storage dtype ('auto' is never
        # surfaced — what the pool actually stores is): exactly one dtype
        # label reads 1, so a scrape proves which encoding served.
        _kv_name = jnp.dtype(kv_pool_dtype).name
        self.telemetry['kv_cache_dtype'] = _kv_name
        for _dt in _metrics.KV_CACHE_DTYPE_LABELS:
            _metrics.KV_CACHE_DTYPE_INFO.labels(dtype=_dt).set(
                1.0 if _dt == _kv_name else 0.0
            )
        if _kv_name not in _metrics.KV_CACHE_DTYPE_LABELS:
            _metrics.KV_CACHE_DTYPE_INFO.labels(dtype='other').set(1.0)
        if cfg.quantization and hasattr(model, 'qmm_backend'):
            self.telemetry['qmm_backend'] = model.qmm_backend
        if (
            self._own_params
            and mesh is None
            and jax.devices()[0].platform == 'tpu'
        ):
            # Let XLA pick the weight layouts the decode loop wants and
            # store the params that way at rest. Without this, XLA inserts
            # layout-conversion copies of the stacked q/k/v kernels (1.5 GB
            # at 7B dims) inside every window dispatch — enough to overflow
            # a v5e's HBM next to the weights, and pure wasted bandwidth.
            # Prefill is layout-agnostic (0.13 GiB of temporaries either
            # way when it was measured, on older code), so the migrated
            # layout serves every executable.
            # A compile failure here raises: serving 7B on the default
            # layout is the HBM overflow described above, not a fallback.
            with self._compile_watcher.phase(
                'auto_layout', f'b{cfg.max_num_seqs}',
                scope=self._compile_scope,
            ):
                compiled, formats = self._compile_auto_layout(window_fn)
            # Destructive from here on (source leaves are deleted as they
            # migrate); a failure leaves the engine unusable and callers
            # rebuild with fresh params.
            with self._compile_watcher.phase(
                'migrate_params', 'params', compiles=False,
                scope=self._compile_scope,
            ):
                self.params = self._migrate_params(formats)
            self._decode_window = compiled
            self._pin_mixed_layout(formats)
            self._pin_spec_layout(formats)
        with self._compile_watcher.phase(
            'kv_allocate', f'blocks{cfg.num_blocks}', compiles=False,
            scope=self._compile_scope,
        ):
            self.kv.allocate()
            if self.window_kv is not None:
                self.window_kv.allocate()
        if len(spec.paged) > 1 or spec.latent or spec.state is not None:
            self.telemetry['kv_pools'] = {
                group.name: {
                    'layers': group.num_layers, 'window': group.window,
                    'blocks': kv.num_blocks, 'bytes': kv.hbm_bytes,
                    # a block as a layer's buffer stores it
                    'block_shape': list(kv.pool_shape[2:]),
                }
                for group, kv in zip(spec.paged, (self.kv, self.window_kv))
            }
        # Keys a step of the paged kernel's row walk over each pool (decode
        # calls under the Pallas backends: ``ops.paged_attention.
        # walk_keys_a_step``, capped by the table as the kernel caps it);
        # ``kv_chunks*`` on the decode records is reckoned with them.
        self._walk_keys = {}
        if attn_backend in ('pallas', 'interpret'):
            self._walk_keys = {
                group.name: min(
                    walk_keys_a_step(
                        kv.pool_shape[-1], kv.dtype,
                        planes=1 if group.row else 2,
                        block_size=cfg.block_size,
                    ),
                    self.max_blocks_per_seq * cfg.block_size,
                )
                for group, kv in zip(spec.paged, (self.kv, self.window_kv))
            }
            self.telemetry['kv_walk_keys'] = dict(self._walk_keys)
        # The form of the walk's softmax block in each group's decode calls
        # (``ops.paged_attention.walk_block``, the rule the kernel traces
        # with: KV heads and queries a head, a block of positions folded
        # in); ``walk_block*`` on the decode records, named as ``kv_chunks*``.
        self._walk_block_fields = {}
        if self._walk_keys:
            heads = model_cfg.num_heads  # a count, or one a group's name
            forms = {
                group.name: walk_block_form(
                    (heads(group.name) if callable(heads) else heads)
                    * spec.block,
                    group.stored_row or model_cfg.head_size,
                    kv.pool_shape[-1],
                )
                for group, kv in zip(spec.paged, (self.kv, self.window_kv))
            }
            self.telemetry['walk_block'] = forms
            self._walk_block_fields = {
                self._group_field('walk_block', group): forms[group.name]
                for group in spec.paged
            }
        # The form of the routed experts' matmuls in each program a
        # dispatch can run (``models.moe.expert_form``, the rule the
        # programs themselves trace with: static shapes alone). A config
        # that says which experts this chip holds is one of the families
        # that route through ``models/moe.py``; ``moe_form`` on the decode
        # and prefill records is looked up by the dispatch's row count.
        self._moe_widths = None
        if getattr(model_cfg, 'first_local_expert', None) is not None:
            self._moe_widths = moe.bank_widths(self.params)
        # the prefill programs a dispatch can run: name -> (span, rows)
        prefills = {}
        for bucket in self.prefill_buckets:
            rows = 1
            while rows <= self._prefill_batch_cap(bucket):
                prefills[f'prefill({bucket}, {rows})'] = (bucket, rows)
                rows *= 2
        if self._moe_widths is not None:
            rows = cfg.max_num_seqs
            tokens = {f'decode({rows})': rows * self._block} | {
                key: span * rows for key, (span, rows) in prefills.items()
            }
            forms = {key: self._moe_form(n) for key, n in tokens.items()}
            self.telemetry['moe_form'] = forms
            # The grouped programs' kernel tiles (row tile, gate/up and
            # down column tiles: ``moe.grouped_tiles``, the rule they trace
            # with), or 'xla' where the backend keeps ``ragged_dot``.
            self.telemetry['moe_grouped_tiles'] = {
                key: moe.grouped_tiles(
                    tokens[key], model_cfg.experts_per_token,
                    *self._moe_widths[2:],
                ) or 'xla'
                for key, form in forms.items() if form == 'grouped'
            }
        # What a family says of the forms its prefill programs take (its
        # config's ``prefill_forms``, the rules the programs themselves
        # trace with): telemetry keys of its own, written once here.
        family_forms = getattr(model_cfg, 'prefill_forms', None)
        if family_forms is not None:
            self.telemetry.update(family_forms(prefills))
        if self.state_pool is not None:
            with self._compile_watcher.phase(
                'state_allocate', f'slots{cfg.max_num_seqs}', compiles=False,
                scope=self._compile_scope,
            ):
                self.state_pool.allocate()
            # One account of the pool; its slots and bytes also under the
            # two older scalar keys, which granite's benchmark driver and
            # chip_smoke.py read.
            pool = self.telemetry['state_pool'] = self.state_pool.describe()
            self.telemetry['state_pool_slots'] = pool['slots']
            self.telemetry['state_pool_bytes'] = pool['bytes']
        # Merge host-known overrides (fresh admissions) into the device-
        # carried last-token vector between pipelined windows.
        self._merge_ids = jax.jit(
            lambda carried, mask, vals: jnp.where(mask, vals, carried)
        )
        self._write_prefill = jax.jit(
            _write_prefill_all_layers, donate_argnums=(0, 1)
        )
        self._sample = jax.jit(
            lambda lg, t, tp, mp, tk, seeds, counters: sample_tokens(
                lg, None, t, tp, mp,
                top_window=cfg.sampling_top_window, top_k=tk,
                row_keys=fold_row_keys(seeds, counters),
            )
        )
        # Tokens dispatched on device but not yet fetched, per request —
        # the pipelined path's lag bookkeeping.
        self._unacked: dict[int, int] = {}
        # Requests whose uncached prefill tail rides mixed windows, in
        # FIFO dispatch order (rids; entries are dropped at final-chunk
        # processing, preemption, or lazily when a request vanishes).
        self._prefilling: list[int] = []
        # Set by _run_to_completion: lets chunked prefill retire one
        # in-flight decode window between chunks.
        self._drain_hook = None
        # Windows in flight behind a dispatch: depth - 1 inside the
        # pipelined loop, 0 under step(). A row's blocks come back that
        # many dispatches after its last window (the decode-budget walk).
        self._windows_behind = 0
        # The decode-budget look-ahead's verdict on the waiting head at
        # the latest admission attempt (True = it made the head wait).
        self._head_deferred = False
        # Device-side last-token vector carried across the pipelined loop;
        # deferred prefill scatters freshly sampled first tokens into it.
        self._carried = None
        self._scatter_tokens = jax.jit(
            lambda carried, slot_idx, toks: carried.at[slot_idx].set(toks)
        )
        # Serving-path attribution (docs/observability.md): a runtime-
        # flippable flag (no compiled shapes depend on it), the analytic
        # roofline cost model priced from the FINAL params (post-quant,
        # post-relayout — the bytes that really stream), and per-kind
        # accumulators behind roofline_summary(). Cost-model failures
        # (exotic leaf types) disable the gauges, never the engine.
        self.attribution = cfg.attribution
        # What the stall watcher may ask from its own thread
        # (``stall_context``): the thread that last opened a root span
        # here, the windows dispatched and not yet fetched, and the one
        # whose fetch is under way. Built with attribution on, the engine
        # is watched until ``shutdown()``.
        self._serve_thread: int | None = None
        self._inflight = ()
        self._fetching: dict | None = None
        if cfg.attribution:
            get_stall_watchdog().watch(self)
        self._cost_model = None
        self._roofline: dict[str, dict[str, float]] = {}
        # Measured executable costs from compiled.cost_analysis(), filled
        # by warmup() (observability/xla_cost.py): the XLA-measured twin
        # of the analytic cost model above, behind the
        # distllm_engine_mfu_measured gauges and the calibration ratios.
        self._measured_costs: dict[str, _xla_cost.XlaCost] = {}
        # Built unconditionally (a cheap metadata walk) so flipping
        # self.attribution ON at runtime works even when the engine was
        # constructed with attribution off.
        try:
            from distllm_tpu.observability.roofline import CostModel

            self._cost_model = CostModel.from_params(
                self.params, cfg.decode_steps,
                # Param leaves report GLOBAL size/bytes under TP; the
                # roofline scales the peaks by the mesh size to match.
                num_devices=mesh.size if mesh is not None else 1,
                # Routed experts: FLOPs count the parameters a token
                # reaches, not the whole bank.
                experts_per_token=getattr(model, 'experts_per_token', None),
                layer_passes=spec.passes,
            )
        except Exception as exc:
            self.telemetry['roofline_fallback'] = repr(exc)[:300]

    @staticmethod
    def _refuse_unservable(
        spec, cfg: EngineConfig, mesh, kv_pool_dtype
    ) -> None:
        """Settings that cannot be right yet with what the model's
        ``cache_spec()`` declares, each refused by name with its reason.

        A model with recurrent state: a sequence is its KV pages AND the
        state at its last token, so whatever reuses, moves or rewinds KV
        blocks without that state would serve wrong tokens. A model with a
        windowed group: a sequence holds only the blocks its next query
        sees, so whatever keeps, shares or moves a sequence's blocks by
        their index in the sequence would find the trash block there."""
        int8 = jnp.dtype(kv_pool_dtype) == jnp.dtype(jnp.int8)
        if spec.state is not None:
            refused = {
                'enable_prefix_cache': cfg.enable_prefix_cache
                and 'a cached block is only a prefix with the recurrent state '
                'at its boundary',
                'host_kv_tier_bytes': bool(cfg.host_kv_tier_bytes)
                and 'a spilled block is only a prefix with the recurrent state '
                'at its boundary',
                'enable_mixed_batching': cfg.enable_mixed_batching
                and 'chunk rows inside a decode window would have to carry '
                'recurrent state between windows',
                'draft_k': bool(cfg.draft_k)
                and 'a rejected draft would have to rewind the recurrent state',
                'kv_cache_dtype=int8': int8
                and 'the hybrid attention path has no quantized-page route',
                'quantization': bool(cfg.quantization)
                and 'the hybrid parameter tree has no quantized route',
                'mesh': mesh is not None
                and 'the recurrent state pool and the grouped expert matmul '
                'have no partitioning',
            }
            for setting, why in refused.items():
                if why:
                    raise ValueError(
                        f'{setting} cannot serve a hybrid model (recurrent '
                        f'layers beside attention layers): {why}; state '
                        'snapshots are not implemented'
                    )
        if spec.latent:
            if len(spec.paged) > 1 or spec.paged[0].window is not None:
                raise ValueError(
                    'cache groups '
                    f'{[(g.name, g.window, g.row) for g in spec.paged]} '
                    'cannot be served: a latent group holds whole contexts '
                    'and stands alone; latent rows beside other groups or '
                    'behind a window have no allocator yet'
                )
            refused = {
                'kv_cache_dtype=int8': int8
                and 'a latent row is keys and values at once, and '
                'QuantizedKV scales a K head and a V head apart',
                'host_kv_tier_bytes': bool(cfg.host_kv_tier_bytes)
                and 'the host, disk and peer tiers and the .kvblock format '
                'carry a K payload and a V payload a block',
                'enable_prefix_cache': cfg.enable_prefix_cache
                and 'the copy of a shared block on write indexes a stacked '
                'K and V pool, and a latent pool is one buffer a layer',
                'enable_mixed_batching': cfg.enable_mixed_batching
                and 'the mixed window is the K/V family\'s program',
                'draft_k': bool(cfg.draft_k)
                and 'the speculative window is the K/V family\'s program',
                'quantization': bool(cfg.quantization)
                and 'the family\'s parameter trees have no quantized route',
                'mesh': mesh is not None
                and 'one latent head cannot be split over the model axis, '
                'and the grouped expert matmul has no partitioning',
            }
            for setting, why in refused.items():
                if why:
                    raise ValueError(
                        f'{setting} cannot serve a model with a latent '
                        f'cache group: {why}'
                    )
        if spec.passes > 1:
            refused = {
                'enable_mixed_batching': cfg.enable_mixed_batching
                and 'the mixed window is the single-pass K/V family\'s '
                'program',
                'draft_k': bool(cfg.draft_k)
                and 'the speculative window is the single-pass K/V '
                'family\'s program',
                'kv_cache_dtype=int8': int8
                and 'a plane a pass under one scale row a block was never '
                'held to the reference',
                'quantization': bool(cfg.quantization)
                and 'quantized kernels were never run through the loop '
                'over the passes',
                'mesh': mesh is not None
                and 'the loop over the passes was never partitioned',
            }
            for setting, why in refused.items():
                if why:
                    raise ValueError(
                        f'{setting} cannot serve a looped model (its stack '
                        f'runs {spec.passes} times a token): {why}'
                    )
        if spec.block > 1:
            LLMEngine._refuse_for_blocks(spec, cfg, mesh, int8)
        if not spec.windowed:
            return
        if spec.paged[0].window is not None or len(spec.paged) > 2:
            raise ValueError(
                'cache groups '
                f'{[(g.name, g.window) for g in spec.paged]} cannot be '
                'served: the first group must hold whole contexts (its '
                'blocks are the scheduler\'s) and one windowed group may '
                'follow it; windows of several sizes have no allocator yet'
            )
        refused = {
            'host_kv_tier_bytes': bool(cfg.host_kv_tier_bytes)
            and 'a spilled or promoted block names one pool; the host, disk '
            'and peer tiers know no second',
            'enable_prefix_cache': cfg.enable_prefix_cache
            and 'a cached prefix has its windowed blocks freed behind it',
            'enable_mixed_batching': cfg.enable_mixed_batching
            and 'chunk rows inside a decode window would hold window + chunk '
            'blocks that the window\'s admission gate does not count',
            'draft_k': bool(cfg.draft_k)
            and 'a rejected draft rewinds positions past blocks already '
            'freed behind the window',
            'kv_cache_dtype=int8': int8
            and 'a freed block\'s scale row would outlive its holder',
            'quantization': bool(cfg.quantization)
            and 'the family\'s parameter trees have no quantized route',
            'mesh': mesh is not None
            and 'the second pool and the grouped expert matmul have no '
            'partitioning',
        }
        for setting, why in refused.items():
            if why:
                raise ValueError(
                    f'{setting} cannot serve a model with a windowed cache '
                    f'group: {why}'
                )

    @staticmethod
    def _refuse_for_blocks(spec, cfg: EngineConfig, mesh, int8: bool) -> None:
        """What was never held to the reference for a model that decides a
        block of positions together, each refused by name with its reason,
        and the sizes its windows, pages and spans must come in."""
        block = spec.block
        sizes = {
            'decode_steps': cfg.decode_steps,
            'block_size': cfg.block_size,
            'max_model_len': cfg.max_model_len,
            'prefill_chunk_tokens': cfg.prefill_chunk_tokens,
        }
        for setting, size in sizes.items():
            if size % block:
                raise ValueError(
                    f'{setting}={size} cannot serve a model that decides '
                    f'blocks of {block} positions: windows, pages, contexts '
                    'and prefill spans hold whole blocks'
                )
        if not 0 <= cfg.denoise_steps <= block:
            raise ValueError(
                f'denoise_steps={cfg.denoise_steps} cannot serve a model '
                f'that decides blocks of {block} positions: a step decides '
                'at least one'
            )
        refused = {
            'cache groups': (
                len(spec.paged) > 1 or spec.latent or spec.state is not None
                or spec.passes > 1
            ) and 'the block window is written for one stacked K/V pool',
            'draft_k': bool(cfg.draft_k)
            and 'a block is decided by its own forwards, not verified '
            'against a draft',
            'enable_mixed_batching': cfg.enable_mixed_batching
            and 'the mixed window is the one-token K/V family\'s program',
            'enable_prefix_cache': cfg.enable_prefix_cache
            and 'a cached KV block shared under the block-causal mask was '
            'never held to the reference',
            'host_kv_tier_bytes': bool(cfg.host_kv_tier_bytes)
            and 'a promoted KV block under the block-causal mask was never '
            'held to the reference',
            'defer_prefill': cfg.defer_prefill
            and 'a prefill yields no token to defer',
            'kv_cache_dtype=int8': int8
            and 'a block rewritten by its commit would rescale its page '
            'twice',
            'quantization': bool(cfg.quantization)
            and 'the family\'s parameter trees have no quantized route',
            'mesh': mesh is not None
            and 'the grouped expert matmul has no partitioning',
        }
        for setting, why in refused.items():
            if why:
                raise ValueError(
                    f'{setting} cannot serve a model that decides blocks of '
                    f'{block} positions together: {why}'
                )

    def _build_window_group(self, group, pool) -> None:
        """The pool, the allocator and the constants of the windowed
        group. A windowed sequence's demand is constant: ``decode_bound``
        blocks once it decodes, and up to ``bound(bucket)`` while one of
        its prefill spans is dispatched, given back down to the window as
        soon as that dispatch is issued."""
        cfg = self.config

        def bound(span):
            return window_bound(group.window, cfg.block_size, span)

        span = cfg.prefill_chunk_tokens or cfg.max_model_len
        self._window_decode_bound = bound(cfg.decode_steps)
        # What the rows of one prefill dispatch hold above that.
        self._window_prefill_reserve = max(
            self._prefill_batch_cap(bucket)
            * max(0, bound(bucket) - self._window_decode_bound)
            for bucket in self.prefill_buckets
            if bucket <= pick_bucket(span, self.prefill_buckets)
        )
        # The trash block, every slot at its constant, one prefill
        # dispatch: the pool never makes a request wait, so admission asks
        # nothing of it, and ``WindowBlocks.cover`` running short is a bug.
        num_blocks = (
            1 + cfg.max_num_seqs * self._window_decode_bound
            + self._window_prefill_reserve
        )
        self.window_kv = pool(group, num_blocks)
        self.window_blocks = WindowBlocks(
            num_blocks, cfg.block_size, group.window
        )

    def _pools(self):
        """The ``k`` and ``v`` operands of a serving program: the pool's
        arrays, or one entry a paged group."""
        if self.window_kv is None:
            return self.kv.k_pool, self.kv.v_pool
        return (self.kv.k_pool, self.window_kv.k_pool), (self.kv.v_pool, self.window_kv.v_pool)

    def _fold_pools(self, k, v) -> None:
        if self.window_kv is None:
            self.kv.k_pool, self.kv.v_pool = k, v
        else:
            (self.kv.k_pool, self.window_kv.k_pool), (self.kv.v_pool, self.window_kv.v_pool) = k, v

    def _group_tables(self, tables, window_tables=None):
        """The ``bt`` operand: the first group's tables, with the windowed
        group's beside them where there is one (``tables`` again where the
        caller's are all trash)."""
        if self.window_kv is None:
            return tables
        return (tables, tables if window_tables is None else window_tables)

    def _call_prefill_paged(self, ids, pos, bt, ctx, tails, slots=None):
        """Dispatch the paged prefill program over device arrays and fold
        its pools back; returns the last logits. ``slots`` is each row's
        slot of a hybrid's state pool (the pool's size for a pad row)."""
        k, v = self._pools()
        extra = (
            () if self.state_pool is None else (self.state_pool.state, slots)
        )
        last_logits, k, v, *state = self._call(
            self._prefill_paged, self.params, ids, pos, k, v, bt, ctx, tails,
            *extra,
        )
        self._fold_pools(k, v)
        if state:
            (self.state_pool.state,) = state
        return last_logits

    def _moe_form(self, tokens: int) -> str | None:
        """``'dense'`` or ``'grouped'`` for a program of ``tokens`` rows, or
        None for a model without routed experts."""
        if self._moe_widths is None:
            return None
        return moe.expert_form(
            tokens, self.model_cfg.experts_per_token, *self._moe_widths
        )

    def _moe_form_field(self, tokens: int) -> dict:
        form = self._moe_form(tokens)
        return {} if form is None else {'moe_form': form}

    def _call_decode_window(self, *plan):
        """Dispatch the decode window over its plan's device arrays (ids,
        positions, context_lens, block_tables, steps_left, the sampling
        rows) and fold its pools back. Returns ``(tokens, last_ids,
        moe_pairs)``, the last None unless the family counts them (a
        family may return a dict of named int32 counters in their place:
        each becomes a field of the ``decode`` record, a number or a list)."""
        ids, pos, ctx, *rest = plan
        k, v = self._pools()
        extra = () if self.state_pool is None else (self.state_pool.state,)
        tokens, k, v, last_ids, *more = self._call(
            self._decode_window, self.params, ids, pos, ctx, k, v, *rest,
            *extra,
        )
        self._fold_pools(k, v)
        if extra:
            self.state_pool.state = more.pop(0)
        return tokens, last_ids, more[0] if more else None

    def _ids_shape(self, rows: int) -> tuple[int, ...]:
        """Of a window's ``ids`` operand: a row's last token, or for a model
        that decides blocks the given tokens of its first block."""
        return (rows,) if self._block == 1 else (rows, self._block)

    def _put(self, x):
        """Host value → device array, replicated over the mesh under TP."""
        if self._replicated is not None:
            return jax.device_put(x, self._replicated)
        return jnp.asarray(x)

    def _put_many(self, *xs):
        """One batched host→device transfer for a dispatch's plan arrays.

        Every individual ``device_put`` is a separate host→device
        transfer the chip may sit idle behind; a single batched put
        ships a window's plan arrays in one.
        """
        if self._replicated is not None:
            return jax.device_put(tuple(xs), self._replicated)
        return jax.device_put(tuple(xs))

    def _compile_auto_layout(self, window_fn):
        """AOT-compile the decode window with ``Layout.AUTO`` for params.

        Non-destructive: returns ``(compiled_window, chosen_formats)``;
        the caller decides whether to run the destructive migration.
        """
        from jax.experimental.layout import Format

        b = self.config.max_num_seqs
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        f32 = jnp.float32

        def spec(tree):
            return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

        groups = self._group_tables(self.kv, self.window_kv)
        pools = jax.tree.map(lambda pool: pool.spec(), groups)
        shapes = (
            spec(self.params),
            sds(self._ids_shape(b), i32),  # ids
            sds((b,), i32),  # positions
            sds((b,), i32),  # context_lens
            pools,
            jax.tree.map(lambda pool: pool.spec('v'), groups),
            self._group_tables(sds((b, self.max_blocks_per_seq), i32)),
            sds((b,), i32),  # steps_left
            sds((b,), f32),
            sds((b,), f32),
            sds((b,), f32),
            sds((b,), i32),  # top_k
            sds((b,), jnp.uint32),  # seeds
        )
        if self.state_pool is not None:
            shapes += (self.state_pool.spec(),)
        if self._block > 1:
            shapes += (sds((b,), f32),)  # unmask thresholds
        jitted = jax.jit(
            window_fn,
            donate_argnums=self._window_donate,
            in_shardings=(auto_layout_formats(shapes[0]),)
            + (Format(),) * (len(shapes) - 1),
        )
        compiled = jitted.lower(*shapes).compile()
        return compiled, compiled.input_formats[0][0]

    def _migrate_params(self, formats):
        """Move weights into ``formats`` leaf-by-leaf, deleting each source
        buffer as it lands so peak HBM stays ~one-largest-leaf above the
        weights (a whole-tree device_put would transiently need 2x).

        Destructive: a mid-migration failure (e.g. HBM fragmentation)
        leaves already-migrated leaves deleted, so it raises — the engine
        is not usable with half-deleted params and callers must rebuild.
        """
        from jax.experimental.layout import Format
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(jax.devices()[0])

        flat_params, treedef = jax.tree.flatten(self.params)
        flat_formats = treedef.flatten_up_to(formats)
        migrated = []
        relayout = {}
        moved_bytes = 0
        # Device-side relayout needs source + target live at once; for the
        # stacked MLP kernels (3.8 GiB each at 7B dims) that overflows HBM
        # beside the rest of the weights, so big leaves bounce through host
        # RAM instead (~1 s each over the link — one-time at startup).
        bounce_limit = 1 << 30
        try:
            for leaf, fmt in zip(flat_params, flat_formats):
                # input_formats carry layouts without concrete shardings;
                # device_put requires both.
                fmt = Format(fmt.layout, sharding)
                nbytes = getattr(leaf, 'nbytes', 0)
                on_device = isinstance(leaf, jax.Array)
                if nbytes > bounce_limit:
                    # Rebuild ON DEVICE from a host copy: the target
                    # buffer is created directly in the final layout and
                    # filled slice-by-slice with donated updates —
                    # device_put of a whole non-default-layout tensor
                    # stages BOTH a default-layout upload and a relayout
                    # copy (2x the tensor), which overflows HBM beside 7B
                    # weights (on the chip: 3.5 GiB asked for, 2.75 GiB
                    # free, at the tenth of twelve leaves of a checkpoint
                    # loaded from disk). A leaf that is still a host
                    # array — TpuGenerator hands the engine numpy params —
                    # is its own host copy; a device leaf is fetched in
                    # slices along dim 0 (a single multi-GiB d2h exhausts
                    # the backend's staging memory) and freed first.
                    # A slice is one index of dim 0 (a layer of a stacked
                    # kernel: ~100 MB). A leaf of many small rows (a
                    # 261,120-row embedding, the head beside it: 10 KB and
                    # 0.5 MB a row, two dispatches a row each way) moves
                    # in slabs of rows instead, ~64 MiB each, the last one
                    # laid over the end of the one before it so that
                    # every slab has one shape.
                    rows = leaf.shape[0]
                    slab, starts = bounce_slabs(rows, nbytes)
                    if on_device:
                        host = np.empty(leaf.shape, leaf.dtype)
                        if slab == 1:
                            for i in range(rows):
                                host[i] = np.asarray(leaf[i])
                        else:
                            take = jax.jit(
                                lambda a, i: jax.lax.dynamic_slice_in_dim(
                                    a, i, slab, 0
                                )
                            )
                            for i in starts:
                                host[i:i + slab] = np.asarray(
                                    take(leaf, np.int32(i))
                                )
                        leaf.delete()
                    else:
                        host = leaf
                    moved = jax.jit(
                        lambda shape=leaf.shape, dtype=leaf.dtype: jnp.zeros(
                            shape, dtype
                        ),
                        out_shardings=fmt,
                    )()
                    if slab == 1:
                        fill = jax.jit(
                            lambda buf, part, idx: jax.lax.dynamic_update_index_in_dim(
                                buf, part, idx, 0
                            ),
                            donate_argnums=0,
                            out_shardings=fmt,
                        )
                        for i in range(host.shape[0]):
                            moved = fill(moved, host[i], np.int32(i))
                    else:
                        fill = jax.jit(
                            lambda buf, part, idx: jax.lax.dynamic_update_slice_in_dim(
                                buf, part, idx, 0
                            ),
                            donate_argnums=0,
                            out_shardings=fmt,
                        )
                        for i in starts:
                            moved = fill(moved, host[i:i + slab], np.int32(i))
                    del host
                    jax.block_until_ready(moved)
                elif on_device:
                    # Compiled identity relayout, NOT device_put: on the
                    # serving backend a device_put with an explicit
                    # non-default Format can silently keep the source
                    # layout (observed on the stacked f32 scale leaves,
                    # bench run 5 — the cached auto-layout window then
                    # rejects the params at dispatch). XLA always honors
                    # out_shardings; donation bounds the transient to the
                    # target buffer. One jitted identity a layout: the
                    # leaves of one shape that ask for it (a kernel held a
                    # layer an array brings 24) share one compile.
                    layout = str(fmt.layout)
                    if layout not in relayout:
                        relayout[layout] = jax.jit(
                            lambda a: a, donate_argnums=0, out_shardings=fmt
                        )
                    moved = relayout[layout](leaf)
                    moved_bytes += nbytes
                    if moved_bytes > (1 << 30):
                        jax.block_until_ready(moved)
                        moved_bytes = 0
                else:
                    moved = jax.device_put(leaf, fmt)
                    moved_bytes += nbytes
                    if moved_bytes > (1 << 30):
                        jax.block_until_ready(moved)
                        moved_bytes = 0
                migrated.append(moved)
        except Exception as exc:
            raise RuntimeError(
                f'weight layout migration failed after {len(migrated)}/'
                f'{len(flat_params)} leaves; params are partially deleted — '
                'rebuild the engine with fresh params'
            ) from exc
        return jax.tree.unflatten(treedef, migrated)

    def _pin_mixed_layout(self, formats) -> None:
        """Re-jit the mixed window with params pinned to the migrated
        layouts (TPU auto-layout path). Without this, the lazily compiled
        mixed executable would ask for default layouts and XLA would
        insert multi-GiB relayout copies of the stacked kernels inside
        every chunk-carrying window — silently repaying the bandwidth the
        migration bought."""
        if self._mixed_window is None:
            return
        from jax.experimental.layout import Format
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(jax.devices()[0])
        pinned = jax.tree.map(
            lambda fmt: Format(fmt.layout, sharding), formats
        )
        self._mixed_window = jax.jit(
            self._mixed_fn,
            donate_argnums=(4, 5),
            in_shardings=(pinned,) + (Format(),) * 22,
        )

    def _pin_spec_layout(self, formats) -> None:
        """Re-jit the speculative windows with params pinned to the
        migrated layouts (the mixed-window rationale applies unchanged:
        a default-layout lazy compile would bury multi-GiB relayout
        copies inside every verify dispatch)."""
        if self._spec_window is None:
            return
        from jax.experimental.layout import Format
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(jax.devices()[0])
        pinned = jax.tree.map(
            lambda fmt: Format(fmt.layout, sharding), formats
        )
        self._spec_window = jax.jit(
            self._spec_fn,
            donate_argnums=(4, 5),
            in_shardings=(pinned,) + (Format(),) * 12,
        )
        if self._spec_mixed_window is not None:
            self._spec_mixed_window = jax.jit(
                self._spec_mixed_fn,
                donate_argnums=(4, 5),
                in_shardings=(pinned,) + (Format(),) * 22,
            )

    def warmup(self) -> None:
        """Compile every serving shape outside the request path.

        Runs each (batch, bucket) prefill the admission policy can emit,
        the KV scatter, the full-batch decode step, and the per-shape
        samplers on dummy inputs. Block tables are all zero, so every K/V
        write lands in the reserved trash block — scheduler state and real
        cache contents are untouched. Combine with jax's persistent
        compilation cache to make later processes start hot.

        Every shape in the ladder runs under a compile-watcher phase
        (docs/observability.md "Startup & compile attribution"): one
        ``compile`` flight record + ``distllm_compile_seconds{kind,shape,path}``
        observation per (kind, batch, bucket), cache-hit marked on the
        re-warmup / persistent-cache fast paths — so a 22–45 min cold
        warmup (or a wedge inside it) is attributable shape by shape.
        Afterwards the warmed serving executables are priced via
        ``cost_analysis()`` (observability/xla_cost.py) for the measured
        MFU gauges.
        """
        watch = self._compile_watcher
        # Quantized pools compile their own executables for every phase
        # that touches KV (the int8 scatter/dequant graphs are different
        # programs): tag the shape labels so the compile ledger
        # attributes an int8 warmup to the int8 config, not to a
        # mysteriously-recompiling float one.
        qtag = 'q8' if self.kv.quantized else ''
        for bucket in self.prefill_buckets:
            cap = self._prefill_batch_cap(bucket)
            b = 1
            while True:
                ids = np.zeros((b, bucket), np.int32)
                mask = np.ones((b, bucket), np.int32)
                last_pos = np.zeros((b,), np.int32)
                lengths = np.zeros((b,), np.int32)  # all writes -> trash
                block_rows = np.zeros((b, self.max_blocks_per_seq), np.int32)
                # A hybrid has no dense route: all its prefills are paged.
                if self._prefill is not None:
                    with watch.phase(
                        'prefill', f'b{b}x{bucket}{qtag}',
                        scope=self._compile_scope,
                    ):
                        # Through _call, as in serving: a program lowered
                        # again there is compared with the signature it
                        # had here.
                        logits, k_all, v_all = self._call(
                            self._prefill,
                            self.params,
                            self._put(ids),
                            self._put(mask),
                            self._put(last_pos),
                        )
                        self.kv.k_pool, self.kv.v_pool = self._call(
                            self._write_prefill,
                            self.kv.k_pool,
                            self.kv.v_pool,
                            k_all,
                            v_all,
                            self._put(block_rows),
                            self._put(lengths),
                        )
                        np.asarray(self._sample_device(logits, [None] * b))
                if (
                    self.prefix_cache is not None
                    or self.config.prefill_chunk_tokens
                    or self._prefill is None
                ):
                    # Paged-context prefill shapes (cache-hit tails and
                    # chunks dispatch through prefill_paged): tail_lens 0
                    # routes every write to the trash block.
                    with watch.phase(
                        'prefill_paged', f'b{b}x{bucket}{qtag}',
                        scope=self._compile_scope,
                    ):
                        (
                            ids_dev,
                            pos_dev,
                            rows_dev,
                            ctx_dev,
                            tails_dev,
                        ) = self._put_many(
                            ids,
                            np.zeros((b, bucket), np.int32),
                            block_rows,
                            np.ones((b,), np.int32),
                            np.zeros((b,), np.int32),
                        )
                        pg_logits = self._call_prefill_paged(
                            ids_dev, pos_dev, self._group_tables(rows_dev),
                            ctx_dev, tails_dev,
                            # Pad rows: a hybrid's state writes are dropped.
                            self._put(np.full(
                                (b,), self.config.max_num_seqs, np.int32
                            )) if self.state_pool is not None else None,
                        )
                        if self._block == 1:  # a block's prefill samples nothing
                            pg_logits = self._sample_device(pg_logits, [None] * b)
                        np.asarray(pg_logits)
                if b >= cap:
                    break
                b *= 2
        if self.prefix_cache is not None:
            # Warm the COW block copy at its common shape (one hit per
            # dispatch): src = dst = trash block 0 is a state-safe
            # self-copy. Without this, the first aligned full-cover cache
            # hit pays the compile inside the very TTFT the cache exists
            # to shrink.
            with watch.phase('cow_copy', f'b1{qtag}', scope=self._compile_scope):
                src_dev, dst_dev = self._put_many(
                    np.zeros((1,), np.int32), np.zeros((1,), np.int32)
                )
                self.kv.k_pool, self.kv.v_pool = self._cow_copy(
                    self.kv.k_pool, self.kv.v_pool, src_dev, dst_dev
                )
        if self.kv_tier is not None:
            # Warm the tier's gather (spill fetch) / scatter (promotion
            # write-back) pow2 block-count ladder. All indices are the
            # trash block 0, so writes and reads touch no real state;
            # without this the first pool-pressure spill would pay the
            # compile inside the serving loop it interrupts.
            num_layers, _, bs_, folded = self.kv.pool_shape
            n_kv = self.kv.shape[3]
            npad = 1
            cap = self._pow2(self.max_blocks_per_seq)
            while npad <= cap:
                with watch.phase(
                    'tier_promote', f'n{npad}{qtag}',
                    scope=self._compile_scope,
                ):
                    idx = np.zeros((npad,), np.int32)
                    zeros = np.zeros(
                        (num_layers, npad, bs_, folded), dtype=self.kv.dtype
                    )
                    if self.kv.quantized:
                        # Promotion operands for an int8 pool are
                        # QuantizedKV trees: stage zero scale planes
                        # beside the zero data so the warmed executable
                        # matches the serving _begin_promotion shapes.
                        s_zeros = np.zeros(
                            (num_layers, npad, n_kv), np.float32
                        )
                        k_d, v_d, ks_d, vs_d, idx_dev = self._put_many(
                            zeros, zeros, s_zeros, s_zeros, idx
                        )
                        k_dev = QuantizedKV(k_d, ks_d)
                        v_dev = QuantizedKV(v_d, vs_d)
                    else:
                        k_dev, v_dev, idx_dev = self._put_many(
                            zeros, zeros, idx
                        )
                    self.kv.k_pool, self.kv.v_pool = self._write_promoted(
                        self.kv.k_pool, self.kv.v_pool, k_dev, v_dev, idx_dev
                    )
                    gk, gv = self._gather_blocks(
                        self.kv.k_pool, self.kv.v_pool, self._put(idx)
                    )
                    np.asarray(self._probe(self.kv.k_pool))
                    np.asarray(self._probe(gk))
                    np.asarray(self._probe(gv))
                npad *= 2
        bsz = self.config.max_num_seqs
        # Warm the fused decode window: steps_left = 0 freezes every slot,
        # so all KV writes land in the trash block and no state advances.
        with watch.phase(
            'decode_window', f'b{bsz}x{self.config.decode_steps}{qtag}',
            scope=self._compile_scope,
        ):
            tokens, _, _ = self._call_decode_window(
                self._put(np.zeros(self._ids_shape(bsz), np.int32)),
                self._put(np.zeros((bsz,), np.int32)),
                self._put(np.ones((bsz,), np.int32)),
                self._group_tables(self._put(
                    np.zeros((bsz, self.max_blocks_per_seq), np.int32)
                )),
                self._put(np.zeros((bsz,), np.int32)),
                self._put(np.zeros((bsz,), np.float32)),
                self._put(np.ones((bsz,), np.float32)),
                self._put(np.zeros((bsz,), np.float32)),
                self._put(np.zeros((bsz,), np.int32)),
                self._put(np.zeros((bsz,), np.uint32)),
                *(
                    [self._put(np.ones((bsz,), np.float32))]  # thresholds
                    if self._block > 1 else []
                ),
            )
            self._merge_ids(
                self._put(np.zeros((bsz,), np.int32)),
                self._put(np.zeros((bsz,), bool)),
                self._put(np.zeros((bsz,), np.int32)),
            )
            # In-phase completion barrier (every other ladder phase ends
            # with a host fetch): without it the window's async execution
            # tail would be attributed to whatever phase runs next.
            np.asarray(tokens)
        if self._mixed_window is not None and not self.config.draft_k:
            # Warm every mixed-window shape the chunk planner can emit
            # — but NOT in speculative mode: _dispatch_window then always
            # routes to spec windows, so the classic mixed executable is
            # structurally unreachable and each of its bucket shapes
            # would be a multi-minute unrolled-window compile for
            # nothing (chunk traffic rides _spec_mixed_window, warmed
            # below). The jit object still exists — _plan_window_chunks
            # uses it as the mixed-enabled signal — it is just never
            # compiled.
            # rows always pad to the pow2 of max_window_prefill_seqs, so
            # only the chunk-token bucket varies (ladder capped at the
            # window budget). tail_lens 0 + all-zero tables route every
            # write to the trash block; steps_left 0 freezes decode.
            cb = self._mixed_rows()
            span_bucket = pick_bucket(
                self._mixed_span_cap(), self.prefill_buckets
            )
            for bucket in self.prefill_buckets:
                if bucket > span_bucket:
                    break
                with watch.phase(
                    'mixed_window', f'b{bsz}x{bucket}c{cb}{qtag}',
                    scope=self._compile_scope,
                ):
                    mixed_tokens, self.kv.k_pool, self.kv.v_pool, _, _ = (
                        self._mixed_window(
                            self.params,
                            self._put(np.zeros((bsz,), np.int32)),
                            self._put(np.zeros((bsz,), np.int32)),
                            self._put(np.ones((bsz,), np.int32)),
                            self.kv.k_pool,
                            self.kv.v_pool,
                            self._put(
                                np.zeros(
                                    (bsz, self.max_blocks_per_seq), np.int32
                                )
                            ),
                            self._put(np.zeros((bsz,), np.int32)),
                            self._put(np.zeros((bsz,), np.float32)),
                            self._put(np.ones((bsz,), np.float32)),
                            self._put(np.zeros((bsz,), np.float32)),
                            self._put(np.zeros((bsz,), np.int32)),
                            self._put(np.zeros((bsz,), np.uint32)),
                            self._put(np.zeros((cb, bucket), np.int32)),
                            self._put(np.zeros((cb, bucket), np.int32)),
                            self._put(
                                np.zeros(
                                    (cb, self.max_blocks_per_seq), np.int32
                                )
                            ),
                            self._put(np.ones((cb,), np.int32)),
                            self._put(np.zeros((cb,), np.int32)),
                            self._put(np.zeros((cb,), np.float32)),
                            self._put(np.ones((cb,), np.float32)),
                            self._put(np.zeros((cb,), np.float32)),
                            self._put(np.zeros((cb,), np.int32)),
                            self._put(np.zeros((cb,), np.uint32)),
                        )
                    )
                    np.asarray(mixed_tokens)
        if self._spec_window is not None:
            # Warm the speculative verify window: ONE fixed span shape
            # [B, 1 + draft_k] (rows with shorter drafts pad via
            # span_lens, so the span dim never adds compiled shapes).
            # span_lens 0 + all-zero tables route every write to the
            # trash block; logits/tokens are garbage the host discards.
            span = 1 + self.config.draft_k
            with watch.phase(
                'spec_window', f'b{bsz}s{span}{qtag}', scope=self._compile_scope
            ):
                spec_tokens, self.kv.k_pool, self.kv.v_pool, _ = self._spec_window(
                    self.params,
                    self._put(np.zeros((bsz, span), np.int32)),
                    self._put(np.zeros((bsz, span), np.int32)),
                    self._put(np.ones((bsz,), np.int32)),
                    self.kv.k_pool,
                    self.kv.v_pool,
                    self._put(
                        np.zeros((bsz, self.max_blocks_per_seq), np.int32)
                    ),
                    self._put(np.zeros((bsz,), np.int32)),
                    self._put(np.zeros((bsz,), np.float32)),
                    self._put(np.ones((bsz,), np.float32)),
                    self._put(np.zeros((bsz,), np.float32)),
                    self._put(np.zeros((bsz,), np.int32)),
                    self._put(np.zeros((bsz,), np.uint32)),
                )
                np.asarray(spec_tokens)
        if self._spec_mixed_window is not None:
            # Chunk-carrying spec windows: the same chunk-bucket ladder
            # the mixed warmup walks, beside the fixed spec span.
            span = 1 + self.config.draft_k
            cb = self._mixed_rows()
            span_bucket = pick_bucket(
                self._mixed_span_cap(), self.prefill_buckets
            )
            for bucket in self.prefill_buckets:
                if bucket > span_bucket:
                    break
                with watch.phase(
                    'spec_mixed_window', f'b{bsz}s{span}x{bucket}c{cb}{qtag}',
                    scope=self._compile_scope,
                ):
                    spec_tokens, self.kv.k_pool, self.kv.v_pool, _ = (
                        self._spec_mixed_window(
                            self.params,
                            self._put(np.zeros((bsz, span), np.int32)),
                            self._put(np.zeros((bsz, span), np.int32)),
                            self._put(np.ones((bsz,), np.int32)),
                            self.kv.k_pool,
                            self.kv.v_pool,
                            self._put(
                                np.zeros(
                                    (bsz, self.max_blocks_per_seq), np.int32
                                )
                            ),
                            self._put(np.zeros((bsz,), np.int32)),
                            self._put(np.zeros((bsz,), np.float32)),
                            self._put(np.ones((bsz,), np.float32)),
                            self._put(np.zeros((bsz,), np.float32)),
                            self._put(np.zeros((bsz,), np.int32)),
                            self._put(np.zeros((bsz,), np.uint32)),
                            self._put(np.zeros((cb, bucket), np.int32)),
                            self._put(np.zeros((cb, bucket), np.int32)),
                            self._put(
                                np.zeros(
                                    (cb, self.max_blocks_per_seq), np.int32
                                )
                            ),
                            self._put(np.ones((cb,), np.int32)),
                            self._put(np.zeros((cb,), np.int32)),
                            self._put(np.zeros((cb,), np.float32)),
                            self._put(np.ones((cb,), np.float32)),
                            self._put(np.zeros((cb,), np.float32)),
                            self._put(np.zeros((cb,), np.int32)),
                            self._put(np.zeros((cb,), np.uint32)),
                        )
                    )
                    np.asarray(spec_tokens)
        # On this backend block_until_ready does not synchronize; a tiny
        # host fetch is the only reliable completion barrier.
        np.asarray(tokens)
        # Price what XLA actually compiled, now that every serving
        # executable is warm (measured MFU gauges + calibration ratios,
        # docs/observability.md "Measured vs analytic MFU").
        self._price_serving_executables()

    def _pricing_allowed(self, fn) -> bool:
        """Whether pricing ``fn`` via ``lower().compile()`` is safe.

        Already-compiled executables (the TPU auto-layout decode window)
        are free. Re-lowering a ``jax.jit`` wrapper compiles a second
        executable with identical HLO — fine on non-TPU backends (tiny
        compiles) or when the persistent compilation cache will serve it
        from disk, but never worth a second multi-minute unrolled-window
        compile on a cold TPU.
        """
        if isinstance(fn, jax.stages.Compiled):
            return True
        if jax.devices()[0].platform != 'tpu':
            return True
        return bool(jax.config.jax_compilation_cache_dir)

    def _price_serving_executables(self) -> None:
        """Store per-kind :class:`~distllm_tpu.observability.xla_cost.
        XlaCost` for the warmed serving executables — what XLA *measured*
        for one dispatch of each window kind, as opposed to the analytic
        ``CostModel``. ``_record_step`` divides these by each window's
        wall time into the ``distllm_engine_mfu_measured`` gauges and the
        analytic-vs-measured calibration ratios. Pricing is telemetry:
        every failure degrades to a telemetry note, never an error.

        The priced shapes are the serving steady state: full-batch
        decode/spec/mixed windows and the largest prefill shape. Per-kind
        executable cost is fixed per dispatch (frozen slots still pay),
        which is exactly the property that makes it *measured truth* —
        occupancy-dependence lives in the analytic side of the ratio.
        Only decode and (chunk-less) spec have ONE serving shape, so only
        they feed the per-dispatch measured gauges (_record_step);
        prefill/mixed costs are warmup-shape snapshots surfaced via
        :meth:`measured_costs` alone.
        """
        if self._cost_model is None:
            return
        if (
            self.state_pool is not None or self.window_kv is not None
            or self.cache_spec.latent or self._block > 1
        ):
            self.telemetry.setdefault(
                'xla_cost_skipped', 'hybrid programs are not priced'
                if self.state_pool is not None
                else 'programs over several cache groups, over a latent one '
                'or over blocks of positions are not priced'
            )
            return
        cfg = self.config
        bsz = cfg.max_num_seqs

        def zi(*shape):
            return self._put(np.zeros(shape, np.int32))

        def oi(*shape):
            return self._put(np.ones(shape, np.int32))

        def zf(*shape):
            return self._put(np.zeros(shape, np.float32))

        def of(*shape):
            return self._put(np.ones(shape, np.float32))

        def zu(*shape):
            return self._put(np.zeros(shape, np.uint32))

        bt = zi(bsz, self.max_blocks_per_seq)
        targets: list[tuple[str, object, tuple]] = []
        bucket = self.prefill_buckets[-1]
        pb = self._prefill_batch_cap(bucket)
        targets.append((
            'prefill',
            self._prefill,
            (self.params, zi(pb, bucket), oi(pb, bucket), zi(pb)),
        ))
        targets.append((
            'decode',
            self._decode_window,
            (self.params, zi(bsz), zi(bsz), oi(bsz), self.kv.k_pool, self.kv.v_pool,
             bt, zi(bsz), zf(bsz), of(bsz), zf(bsz), zi(bsz), zu(bsz)),
        ))
        if self._spec_window is not None:
            span = 1 + cfg.draft_k
            targets.append((
                'spec',
                self._spec_window,
                (self.params, zi(bsz, span), zi(bsz, span), oi(bsz),
                 self.kv.k_pool, self.kv.v_pool, bt, zi(bsz), zf(bsz), of(bsz),
                 zf(bsz), zi(bsz), zu(bsz)),
            ))
        if self._mixed_window is not None and not cfg.draft_k:
            span_bucket = pick_bucket(
                self._mixed_span_cap(), self.prefill_buckets
            )
            buckets = [bk for bk in self.prefill_buckets if bk <= span_bucket]
            if buckets:
                cb, mb = self._mixed_rows(), buckets[-1]
                targets.append((
                    'mixed',
                    self._mixed_window,
                    (self.params, zi(bsz), zi(bsz), oi(bsz), self.kv.k_pool,
                     self.kv.v_pool, bt, zi(bsz), zf(bsz), of(bsz), zf(bsz),
                     zi(bsz), zu(bsz),
                     zi(cb, mb), zi(cb, mb), zi(cb, self.max_blocks_per_seq),
                     oi(cb), zi(cb), zf(cb), of(cb), zf(cb), zi(cb),
                     zu(cb)),
                ))
        for kind, fn, args in targets:
            try:
                if not self._pricing_allowed(fn):
                    self.telemetry.setdefault(
                        'xla_cost_skipped',
                        'cold-TPU jit executables not re-lowered; seed the '
                        'persistent compilation cache to price them',
                    )
                    continue
                cost = _xla_cost.price_callable(fn, *args)
            except Exception as exc:
                self.telemetry.setdefault(
                    'xla_cost_fallback', repr(exc)[:200]
                )
                continue
            if cost is not None:
                self._measured_costs[kind] = cost
                bytes_accessed = cost.to_dict().get('bytes_accessed')
                if bytes_accessed:
                    # Scrape-visible per-dispatch byte traffic: the KV-
                    # sensitive roofline numerator (an int8 pool shows as
                    # the decode/mixed kinds dropping by the KV share).
                    _metrics.ENGINE_KV_DISPATCH_BYTES.labels(
                        kind=kind
                    ).set(float(bytes_accessed))

    def measured_costs(self) -> dict[str, dict]:
        """XLA-measured per-dispatch executable cost by window kind
        (``{'flops', 'bytes_accessed', 'source'}``; filled by
        :meth:`warmup`, empty before it or when pricing was skipped) —
        the measured side of the roofline calibration ratios."""
        return {k: c.to_dict() for k, c in self._measured_costs.items()}

    # ------------------------------------------------------------- requests
    def add_request(
        self, prompt_ids: list[int], params: SamplingParams | None = None
    ) -> int:
        if not prompt_ids:
            raise ValueError('empty prompt')
        # Reserve room for at least one generated token.
        prompt_ids = prompt_ids[-(self.config.max_model_len - 1) :]
        needed = self.kv.blocks_needed(len(prompt_ids) + 1)
        if needed > self.kv.num_blocks - 1:  # block 0 is reserved
            raise ValueError(
                f'prompt needs {needed} KV blocks but the pool only has '
                f'{self.kv.num_blocks - 1}; increase num_blocks'
            )
        if self.admission_control:
            # May raise EngineOverloaded (honest backpressure) BEFORE any
            # engine state is touched — a shed request owns nothing.
            self._maybe_shed(len(prompt_ids))
        from distllm_tpu.observability.tracing import current_request_id

        request = Request(
            request_id=next(self._next_id),
            prompt_ids=list(prompt_ids),
            params=params or SamplingParams(),
            t_enqueue=time.monotonic(),
            # Bound by the server's request_scope (X-Request-Id) when the
            # add happens inside one; None for offline/batch callers.
            trace_id=current_request_id(),
        )
        request.sample_seed = _request_seed(
            self.config.seed, request.request_id,
            request.params.seed,
        )
        if (
            self.config.draft_k
            and self.config.spec_draft_source == 'prompt_lookup'
        ):
            # Greedy rows verify by argmax comparison; temperature > 0
            # rows verify by device-side rejection sampling against the
            # filtered target (docs/speculative.md "Sampled
            # verification") — both draft from the same n-gram lookup.
            from distllm_tpu.generate.engine.spec import PromptLookupDrafter

            request.drafter = PromptLookupDrafter(self.config.spec_ngram)
        cached_blocks: list[int] = []
        if self.prefix_cache is not None:
            bs = self.config.block_size
            request.digests = block_digests(request.prompt_ids, bs)
            matched = self.prefix_cache.acquire(
                request.request_id, request.digests
            )
            if matched and len(matched) * bs == len(prompt_ids):
                # Aligned full-cover hit: every prompt block is cached,
                # but prefill must still produce last-token logits and
                # the last token's K write would land INSIDE the shared
                # final block. Keep the match, re-prefill only the last
                # token, and copy-on-write that block at dispatch.
                request.cow_src_block = matched[-1]
                cached_blocks = matched[:-1]
                request.num_cached_tokens = len(prompt_ids) - 1
            else:
                cached_blocks = matched
                request.num_cached_tokens = len(matched) * bs
            request.num_borrowed_blocks = len(cached_blocks)
            if matched:
                _metrics.PREFIX_TIER_HITS.labels(tier='hbm').inc(
                    len(matched)
                )
            if self.kv_tier is not None and request.cow_src_block is None:
                # Tier walk past the HBM hit: later digests still in the
                # host/disk tier extend the cached prefix via promotion
                # (begun at admission). Capped so at least one prompt
                # token stays uncached — prefill needs a tail to produce
                # last-token logits from (the HBM full-cover case routes
                # through COW instead; a chain split by partial eviction
                # stops the walk at the first block neither tier holds).
                promo: list[bytes] = []
                for digest in request.digests[len(cached_blocks):]:
                    if self.kv_tier.lookup(digest) is None:
                        break
                    promo.append(digest)
                while promo and (
                    (len(cached_blocks) + len(promo)) * bs
                    >= len(prompt_ids)
                ):
                    promo.pop()
                request.promo_digests = promo
            elif self.kv_tier is None and len(matched) < len(request.digests):
                _metrics.PREFIX_TIER_MISSES.labels(tier='hbm').inc()
            _metrics.PREFIX_LOOKUP_TOKENS.inc(len(prompt_ids))
            if request.num_cached_tokens:
                _metrics.PREFIX_HIT_TOKENS.inc(request.num_cached_tokens)
                self._stats['prefix_hit_tokens'] += request.num_cached_tokens
            self._stats['prefix_lookup_tokens'] += len(prompt_ids)
        self._requests[request.request_id] = request
        self.sched.add(request.request_id, request.num_tokens, cached_blocks)
        _metrics.ENGINE_REQUESTS_ADDED.inc()
        _metrics.ENGINE_PROMPT_TOKENS.inc(len(prompt_ids))
        return request.request_id

    # ------------------------------------- SLO-aware admission (shedding)
    def _ewma_update(
        self, key: str, value: float, alpha: float = 0.25
    ) -> None:
        prev = self._ewma.get(key)
        self._ewma[key] = (
            value if prev is None else prev + alpha * (value - prev)
        )

    def _load_view(self) -> EngineLoadView:
        """Snapshot of engine load for the TTFT predictor
        (resilience/admission.py): scheduler backlog plus EWMA-measured
        per-token prefill time and window cadence, falling back to the
        analytic roofline floors before the first windows land.

        The request scan is O(live requests) per arrival, and that is
        self-limiting BY the policy it feeds: shedding caps the waiting
        backlog near the SLO-equivalent token budget
        (``slo_s / prefill_s_per_token``), so the scan cost is bounded
        by the configured SLO, not by offered load — incremental
        counters would trade that bound for drift risk across
        admit/preempt/quarantine paths."""
        cfg = self.config
        waiting_tokens = 0
        pending_decode = 0
        for r in self._requests.values():
            if r.state is RequestState.WAITING:
                waiting_tokens += r.num_tokens
                pending_decode += r.params.max_tokens
            elif r.state is RequestState.RUNNING:
                pending_decode += max(
                    0, r.params.max_tokens - len(r.output_ids)
                )
        per_tok = self._ewma.get('prefill_s_per_token')
        window_s = self._ewma.get('window_s')
        if (
            per_tok is None or window_s is None
        ) and self._cost_model is not None:
            cm = self._cost_model
            if per_tok is None:
                per_tok = 2.0 * cm.n_params / cm.peak_flops
            if window_s is None:
                window_s = (
                    cm.weight_bytes * cm.decode_steps / cm.peak_hbm_bytes
                )
        return EngineLoadView(
            waiting_tokens=waiting_tokens,
            pending_decode_tokens=pending_decode,
            num_waiting=self.sched.num_waiting,
            num_running=self.sched.num_running,
            max_num_seqs=cfg.max_num_seqs,
            decode_steps=cfg.decode_steps,
            prefill_s_per_token=per_tok or 0.0,
            window_s=window_s or 0.0,
            slo_s=cfg.ttft_slo_s,
        )

    def _maybe_shed(self, prompt_tokens: int) -> None:
        """Shed at enqueue when the predicted TTFT busts the SLO —
        429-style honest backpressure instead of queueing a request into
        a guaranteed miss (docs/resilience.md "Shedding policy")."""
        admit, predicted, retry_after = shed_decision(
            self._load_view(), prompt_tokens
        )
        _metrics.RESILIENCE_PREDICTED_TTFT.observe(predicted)
        if admit:
            return
        _metrics.RESILIENCE_SHED.labels(reason='overload').inc()
        self._stats['shed_requests'] += 1
        self.flight.record(
            'shed',
            reason='overload',
            predicted_ttft_s=round(predicted, 6),
            retry_after_s=round(retry_after, 3),
            prompt_tokens=prompt_tokens,
            queue_depth=self.sched.num_waiting,
        )
        raise EngineOverloaded(
            predicted, retry_after, self.config.ttft_slo_s
        )

    @property
    def has_unfinished(self) -> bool:
        return self.sched.has_unfinished

    # ------------------------------------------------------------ scheduling
    def _admit(self, defer_to=None) -> list[tuple[int, int]]:
        """Admit waiting requests while the scheduler allows.

        Returns the first tokens emitted by prefill as (request_id, token)
        (empty in deferred mode — they surface when the caller processes
        the in-flight records in ``defer_to``). Admissible requests are
        batch-planned: grouped by prompt-length bucket and prefilled
        together in one padded dispatch (under many short requests — the
        MCQA pattern — per-sequence prefill serializes admission behind
        dispatch latency). A synchronous prefill may immediately finish
        its request (stop token / max_tokens=1), freeing slots, so the
        admit→prefill cycle repeats until the scheduler yields nothing.
        """
        # Retire landed tier promotions FIRST (non-blocking poll): their
        # prefill tails are the oldest admitted work, and a promotion
        # begun last cycle has had at least one decode window of
        # transfer overlap by now.
        emitted: list[tuple[int, int]] = list(
            self._finish_promotions(defer_to, may_block=False)
        )
        # Recovery: prefills whose earlier dispatch failed re-run before
        # anything else — their requests hold admitted slots and blocks,
        # and stay decode-gated until this succeeds.
        emitted.extend(self._retry_pending_prefills(defer_to))
        admitted_any = False
        while True:
            admitted: list[Request] = []
            while (rid := self._admit_next_evicting()) is not None:
                request = self._requests[rid]
                request.state = RequestState.RUNNING
                request.admit_tokens = request.num_tokens
                if request.t_admit == 0.0:  # first admission only, not
                    request.t_admit = time.monotonic()  # preemption retries
                    _metrics.REQUEST_QUEUE_WAIT.observe(
                        request.t_admit - request.t_enqueue
                    )
                admitted.append(request)
            if not admitted:
                # Exit poll: promotions begun THIS call whose transfer
                # already landed (is_ready — synchronous backends, or a
                # transfer that raced ahead) prefill now instead of
                # waiting a full loop cycle; in-flight ones keep
                # overlapping with the windows the caller dispatches.
                # Blocking is allowed only when this call admitted
                # nothing — if it did, that freshly dispatched prefill
                # work deserves its chance to overlap the transfer, and
                # the next cycle's exit poll is the backstop.
                emitted.extend(
                    self._finish_promotions(
                        defer_to, may_block=not admitted_any
                    )
                )
                return emitted
            admitted_any = True
            groups: dict[int, list[Request]] = {}
            paged: list[Request] = []
            chunk = self.config.prefill_chunk_tokens
            # Mixed batching: once windows are flowing, admitted tails ride
            # them as chunk rows instead of standalone dispatches. Decided
            # once per admitted batch — at cold start nothing is decoding,
            # so the first batch prefills standalone and bootstraps the
            # stream the rest ride.
            ride = (
                self.config.enable_mixed_batching and self._mixed_can_ride()
            )
            for request in admitted:
                if request.promo_digests and self._begin_promotion(request):
                    # Host-tier hit: the block transfer is in flight and
                    # the request waits (non-decode-ready, no prefill)
                    # until _finish_promotions retires it next cycle.
                    continue
                # Re-prefill covers generated tokens too (recompute
                # preemption path) but never the cached prefix — tail-only
                # prefill is the prefix cache's whole win.
                tail = request.num_tokens - request.num_cached_tokens
                paged_route = bool(
                    request.num_cached_tokens or (chunk and tail > chunk)
                    or self._prefill is None
                )
                if ride and paged_route:
                    # Only paged-route tails ride windows: their spans go
                    # through the SAME ragged write-then-attend kernel as
                    # the standalone paged dispatch (key extent is always
                    # the full padded table), so mixed on/off stay bit-
                    # identical even in bf16. Fresh short prompts keep the
                    # batched dense prefill — a different kernel (bf16
                    # bits differ at scale) AND the better dispatch: one
                    # padded batch beats trickling them through budget-
                    # limited windows.
                    self._enroll_mixed(request)
                    continue
                if paged_route:
                    paged.append(request)
                    continue
                bucket = pick_bucket(tail, self.prefill_buckets)
                groups.setdefault(bucket, []).append(request)
            for bucket, requests in sorted(groups.items()):
                cap = self._prefill_batch_cap(bucket)
                for i in range(0, len(requests), cap):
                    self._stats['prefill_dispatches'] += 1
                    _metrics.ENGINE_PREFILL_DISPATCHES.inc()
                    emitted.extend(
                        self._run_prefill_batch(
                            requests[i : i + cap], bucket, defer_to
                        )
                    )
            emitted.extend(self._run_prefill_paged(paged, defer_to))

    def _admit_next_evicting(self) -> int | None:
        """``admit_next`` behind the decode-budget gate, with prefix-cache
        eviction pressure: when admission stalls on blocks while
        unreferenced cached blocks exist, evict just enough (LRU) and
        retry."""
        if not self._decode_budget_admits():
            return None
        while True:
            try:
                rid = self.sched.admit_next()
            except SchedulerExhausted:
                if not self._evict_for_admission():
                    raise
                continue
            if rid is not None:
                return rid
            if not self._evict_for_admission():
                return None

    def _decode_budget_admits(self) -> bool:
        """May the waiting head join the running rows? Only if the pool
        can carry all of them to the end of their budgets
        (``scheduler.decode_budget_fits``; the policy is at the top of
        scheduler.py). With nothing running the head is always tried."""
        self._head_deferred = False
        head = self.sched.waiting_head()
        running = self.sched.running()
        if head is None or not running:
            return True
        if len(running) >= self.config.max_num_seqs:
            return True  # no slot: the scheduler's own deferral
        rows = [self._budget_row(self._requests[rid]) for _, rid in running]
        rows.append(self._budget_row(self._requests[head]))
        spare = self.sched.num_free_blocks
        if self.prefix_cache is not None:
            spare += self.prefix_cache.num_evictable
        if decode_budget_fits(
            rows, spare, self.config.block_size, self._window_steps,
            self._windows_behind,
        ):
            return True
        self._head_deferred = True
        _metrics.SCHED_DEFERRED.labels(reason='decode_budget').inc()
        self._stats['budget_deferrals'] += 1
        request = self._requests[head]
        if not request.budget_deferred:
            request.budget_deferred = True
            self._stats['budget_deferred_requests'] += 1
        return False

    def _head_waits_on_inflight(self) -> bool:
        """Did the look-ahead just make the head wait while a running
        row has its last tokens in flight? That row's blocks come back
        when its window is fetched, so the pipelined loop fetches before
        it dispatches: the head then joins the next window, not the one
        after, and no window carries only the rows that outlive a wave."""
        if not self._head_deferred:
            return False
        k = self.config.decode_steps
        return any(
            unacked and not self._window_budget(
                self._requests[rid], unacked, k
            )
            for rid, unacked in self._unacked.items()
            if rid in self._requests
        )

    @property
    def _window_steps(self) -> int:
        """Tokens a row can emit in one window (a speculative window
        emits up to ``1 + draft_k``)."""
        cfg = self.config
        return 1 + cfg.draft_k if cfg.draft_k else cfg.decode_steps

    def _budget_row(self, request: Request) -> BudgetRow:
        """The request as the decode-budget walk sees it: where it is,
        how far it is expected to go, what it holds and what it keeps."""
        rid = request.request_id
        tokens = request.num_tokens
        generated = len(request.output_ids)
        left = self._budget_left(request)
        unacked = self._unacked.get(rid, 0)
        use = self._ewma.get('budget_use', 1.0)
        if use < 1.0:
            # Budgets that are not tight (max_tokens far beyond where
            # answers stop): walk to the expected end, the share of
            # their budgets that finished requests used. A row already
            # past it is given one more window.
            expected = math.ceil(use * (left + generated)) - generated
            left = min(left, max(expected, unacked + self._window_steps))
        if (
            request.state is RequestState.WAITING
            or tokens == request.admit_tokens
        ):
            # Its prefill is still to run (or rides mixed windows, or
            # waits for a promotion) and emits one token of the budget
            # (none where blocks of positions are decided together).
            first = int(self._block == 1)
            length, steps = tokens + first, left - first
        else:
            length, steps = tokens + unacked, left - unacked
        kept = request.num_borrowed_blocks
        if kept:
            kept -= min(kept, self.prefix_cache.num_sole(rid))
        return BudgetRow(
            length, max(0, steps), len(self.sched.block_row(rid)), kept
        )

    def _budget_use_was_low(self) -> None:
        """The pool ran short under rows the walk had admitted: the
        expected share of a budget was too low, so double it (capped at
        the whole budget, where the walk is exact)."""
        if 'budget_use' in self._ewma:
            self._ewma['budget_use'] = min(
                1.0, 2.0 * self._ewma['budget_use']
            )

    def _evict_for_admission(self) -> bool:
        if (
            self.prefix_cache is None
            or not self.prefix_cache.num_evictable
            or not self.sched.num_waiting
            # admit_next() returning None conflates "no free slot" with
            # "block shortfall"; when every slot is busy, eviction cannot
            # admit anything and would only flush warm prefixes the next
            # turn needs.
            or self.sched.num_running >= self.config.max_num_seqs
        ):
            return False
        # Worst-case shortfall over waiting requests: evicting a few
        # blocks too many only costs cache entries, never correctness.
        need = 0
        for request in self._requests.values():
            if request.state is not RequestState.WAITING:
                continue
            short = self.kv.blocks_needed(request.num_tokens + 1) - len(
                self.sched.block_row(request.request_id)
            )
            need = max(need, short)
        return self._evict_cached_blocks(need - self.sched.num_free_blocks) > 0

    def _evict_cached_blocks(self, shortfall: int) -> int:
        """Evict up to ``shortfall`` LRU cache blocks into the scheduler's
        free list; returns how many were actually freed. With the host KV
        tier enabled the evicted blocks' KV is spilled device→host first
        (eviction cascades HBM → host → disk → drop); without it the KV
        is dropped outright — counted, never silent."""
        if self.prefix_cache is None or shortfall <= 0:
            return 0
        entries = self.prefix_cache.evict_entries(shortfall)
        if not entries:
            return 0
        if self.kv_tier is not None:
            self._spill_blocks(entries)
        else:
            # HBM is the only tier: this eviction loses the KV for good.
            _metrics.PREFIX_TIER_DROPPED_BLOCKS.inc(len(entries))
        freed = [bid for _, bid in entries]
        self.sched.release_blocks(freed)
        self._stats['prefix_evicted_blocks'] += len(freed)
        return len(freed)

    # ------------------------------------------------- host/disk KV tier
    @staticmethod
    def _pow2(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _spill_blocks(self, entries: list[tuple[bytes, int]]) -> None:
        """Fetch the evicted blocks' KV device→host in padded gathers and
        adopt them into the host tier, clamped per gather to the pow2
        ladder :meth:`warmup` compiled — a multi-row reservation
        shortfall can evict more blocks than max_blocks_per_seq, and an
        unwarmed gather shape would stall the serving loop on a compile."""
        cap = self._pow2(self.max_blocks_per_seq)
        for start in range(0, len(entries), cap):
            self._spill_chunk(entries[start : start + cap])

    def _spill_chunk(self, entries: list[tuple[bytes, int]]) -> None:
        """One padded device→host gather of ``entries``' KV — the spill
        side's designed host sync: it runs only under pool pressure,
        serializes against at most the in-flight windows, and its cost is
        on the flight ring as the 'spill' record's fetch_s."""
        t_start = time.monotonic()
        n = len(entries)
        npad = self._pow2(n)
        idx = np.zeros((npad,), np.int32)
        for i, (_, bid) in enumerate(entries):
            idx[i] = bid
        k_dev, v_dev = self._gather_blocks(
            self.kv.k_pool, self.kv.v_pool, self._put(idx)
        )
        quantized = isinstance(k_dev, QuantizedKV)
        t_fetch = time.monotonic()
        ks_host = vs_host = None
        with self._span('fetch'):
            # distlint: disable=host-sync-in-hot-path -- the spill tier's ONE designed fetch point: evicted ref==0 blocks must cross to host RAM before their pool blocks are reused, and eviction only fires on pool-pressure shortfalls
            k_host = np.asarray(k_dev.data if quantized else k_dev)
            # distlint: disable=host-sync-in-hot-path -- second half of the same designed spill fetch (V plane of the one padded gather above)
            v_host = np.asarray(v_dev.data if quantized else v_dev)
            # A block leaves the pool in its logical shape (a view of the
            # fetched blocks): what the tiers and ``.kvblock`` files hold.
            k_host = unfold_heads(k_host, self.kv.shape[3])
            v_host = unfold_heads(v_host, self.kv.shape[3])
            if quantized:
                # distlint: disable=host-sync-in-hot-path -- scale rows of the same designed spill fetch (4 bytes per block per KV head, riding the gather already paid for)
                ks_host = np.asarray(k_dev.scale)
                # distlint: disable=host-sync-in-hot-path -- V-side scale rows of the same designed spill fetch
                vs_host = np.asarray(v_dev.scale)
        fetch_s = time.monotonic() - t_fetch
        for i, (digest, _) in enumerate(entries):
            # Per-block copies: LRU eviction must free blocks one at a
            # time, which views over the gathered base array cannot. A
            # quantized pool spills the int8 blocks AS int8 plus their
            # scale rows (half the bytes over the host link; bit-exact
            # on promotion — no dequant/requant round trip).
            if quantized:
                self.kv_tier.put(
                    digest, k_host[:, i].copy(), v_host[:, i].copy(),
                    ks_host[:, i].copy(), vs_host[:, i].copy(),
                )
            else:
                self.kv_tier.put(
                    digest, k_host[:, i].copy(), v_host[:, i].copy()
                )
        self._stats['tier_spills'] += 1
        self._stats['tier_spilled_blocks'] += n
        spilled_bytes = int(k_host[:, :n].nbytes + v_host[:, :n].nbytes)
        if quantized:
            spilled_bytes += int(
                ks_host[:, :n].nbytes + vs_host[:, :n].nbytes
            )
        self.flight.record(
            'spill',
            blocks=n,
            bytes=spilled_bytes,
            fetch_s=round(fetch_s, 6),
            duration_s=round(time.monotonic() - t_start, 6),
            host_tier_blocks=self.kv_tier.num_blocks,
        )

    def _begin_promotion(self, request: Request) -> bool:
        """Start the async promotion of ``request``'s host-tier blocks
        back into the paged pool: device_put the pooled KV, dispatch the
        scatter into the request's own blocks, and ADOPT those blocks
        into the prefix cache immediately (insert + lend_prefix), so they
        are borrowed — counted toward budgets, never freed to the free
        list mid-promotion, surviving preemption like any cached prefix.
        No host sync here: the transfer overlaps in-flight decode windows
        and ``_finish_promotions`` retires it next cycle. Returns False
        when the tier entries vanished (evicted since add_request) — the
        caller falls through to the normal prefill routing."""
        digests = request.promo_digests
        request.promo_digests = []
        rid = request.request_id
        bs = self.config.block_size
        num_layers, _, block_size, n_kv, head_dim = self.kv.shape
        slice_shape = (num_layers, block_size, n_kv, head_dim)
        pool_quantized = self.kv.quantized
        arity = 4 if pool_quantized else 2
        pulled: list[tuple[np.ndarray, ...]] = []
        for digest in digests:
            kv = self.kv_tier.get(digest)
            if kv is None:
                break  # tier-evicted since the add_request walk
            if (
                len(kv) != arity
                or kv[0].dtype != self.kv.dtype
                or kv[0].shape != slice_shape
            ):
                # A spill from a different kv_cache_dtype/geometry config
                # (e.g. bf16 disk files meeting a fresh int8 pool, or the
                # reverse): payload-shape truth beats index membership —
                # treat as a miss and cold-prefill rather than scatter
                # another encoding's bytes into the pool.
                self._stats['tier_payload_mismatches'] += 1
                break
            pulled.append(kv)
        if not pulled:
            return False
        t_start = time.monotonic()
        n = len(pulled)
        digests = digests[:n]
        nb = request.num_borrowed_blocks
        blocks = self.sched.block_row(rid)[nb : nb + n]
        npad = self._pow2(n)
        k_host = np.zeros(
            (num_layers, npad, block_size, n_kv, head_dim),
            dtype=pulled[0][0].dtype,
        )
        v_host = np.zeros_like(k_host)
        ks_host = vs_host = None
        if pool_quantized:
            ks_host = np.zeros((num_layers, npad, n_kv), np.float32)
            vs_host = np.zeros_like(ks_host)
        idx = np.zeros((npad,), np.int32)
        for i, entry in enumerate(pulled):
            k_host[:, i] = entry[0]
            v_host[:, i] = entry[1]
            if pool_quantized:
                ks_host[:, i] = entry[2]
                vs_host[:, i] = entry[3]
            idx[i] = blocks[i]
        t_host = time.monotonic()
        try:
            # Injection site 'device_put': the promotion transfer is the
            # one host→device path that runs against tier state rather
            # than request state, so its failure degrades — the request
            # falls through to cold prefill (return False), counted into
            # distllm_prefix_tier_errors_total{tier="host"}, never raised
            # into admission.
            self._faults.fail('device_put')
            if pool_quantized:
                # Scales stage beside the data planes in the SAME put
                # batch, then ride _write_promoted's tree.map scatter as
                # QuantizedKV leaves — promotion is int8-to-int8
                # bit-exact, scales intact.
                k_dev, v_dev, ks_dev, vs_dev, idx_dev = self._put_many(
                    fold_heads(k_host), fold_heads(v_host), ks_host, vs_host,
                    idx,
                )
                k_dev = QuantizedKV(k_dev, ks_dev)
                v_dev = QuantizedKV(v_dev, vs_dev)
            else:
                # Folded on the host (a view): the blocks enter the pool
                # in the rows it stores.
                k_dev, v_dev, idx_dev = self._put_many(
                    fold_heads(k_host), fold_heads(v_host), idx
                )
            with self._span('promote'):
                self.kv.k_pool, self.kv.v_pool = self._write_promoted(
                    self.kv.k_pool, self.kv.v_pool, k_dev, v_dev, idx_dev
                )
            token = self._probe(self.kv.k_pool)
        except Exception as exc:
            _metrics.PREFIX_TIER_ERRORS.labels(tier='host').inc()
            self._stats['tier_promotion_failures'] += 1
            self.flight.record(
                'event',
                event='promotion_failed',
                rids=[rid],
                blocks=n,
                error=repr(exc)[:200],
            )
            return False
        t_dispatch = time.monotonic()
        # Adopt NOW (not at completion): once inserted + lent the blocks
        # are cache property in both scheduler front-ends — preemption
        # keeps them and dispatch ordering guarantees every later reader
        # sees the scattered KV. First-writer-wins may reject a digest a
        # concurrent request prefilled meanwhile; blocks past the first
        # rejection stay owned (their KV is still valid for THIS row).
        lent = nb
        for digest, bid in zip(digests, blocks):
            if not self.prefix_cache.insert(rid, digest, bid):
                break
            lent += 1
        if lent > nb:
            self.sched.lend_prefix(rid, lent)
            request.num_borrowed_blocks = lent
        request.num_cached_tokens = (nb + n) * bs
        # Decode-readiness gate (the mixed-window mechanism, reused): the
        # request takes no decode steps and no prefill until the
        # promotion retires and its tail prefills.
        request.prefill_target = request.num_tokens
        request.prefill_sent = request.num_cached_tokens
        request.prefill_done = request.num_cached_tokens
        self._promoting[rid] = {
            'token': token,
            'blocks': n,
            'tokens': n * bs,
            't_start': t_start,
            'put_s': round(t_dispatch - t_host, 6),
            'host_s': round(t_host - t_start, 6),
        }
        self._stats['tier_promotions'] += 1
        self._stats['tier_promoted_blocks'] += n
        _metrics.PREFIX_TIER_PROMOTIONS.labels(tier='host').inc(n)
        _metrics.PREFIX_HIT_TOKENS.inc(n * bs)
        self._stats['prefix_hit_tokens'] += n * bs
        return True

    def _finish_promotions(
        self, defer_to=None, may_block: bool = True
    ) -> list[tuple[int, int]]:
        """Retire landed promotions: one audited completion sync per
        promotion (visible as the 'promote' record's wait_s, the put_s
        twin of the window fetch), then prefill the still-uncached tail
        exactly as a plain cache hit would. Non-blocking while other rows
        can make progress — the poll keeps the device_put overlapped with
        decode windows; it hard-waits only when ``may_block`` (the
        caller's admission round produced nothing to overlap with) AND
        every running row is itself waiting on a promotion — the state
        nothing else can advance out of."""
        if not self._promoting:
            return []
        block = may_block and all(
            rid in self._promoting for _, rid in self.sched.running()
        )
        ready: list[Request] = []
        for rid in list(self._promoting):
            record = self._promoting[rid]
            request = self._requests.get(rid)
            if request is None or request.state is not RequestState.RUNNING:
                self._promoting.pop(rid)  # finished/preempted meanwhile
                continue
            token = record['token']
            if not block and not token.is_ready():
                continue  # still in flight; keep overlapping
            t_wait = time.monotonic()
            with self._span('fetch'):
                # distlint: disable=host-sync-in-hot-path -- the promotion path's ONE designed completion sync: a one-element probe of the post-scatter pool proves the promoted KV landed before the tail prefill (and any decode window) reads it
                np.asarray(token)
            wait_s = time.monotonic() - t_wait
            span_s = time.monotonic() - record['t_start']
            self._tier_times['promote_wait_s'] += wait_s
            self._tier_times['promote_span_s'] += span_s
            self._promoting.pop(rid)
            request.prefill_target = 0
            request.prefill_sent = request.num_cached_tokens
            request.prefill_done = request.num_cached_tokens
            ready.append(request)
            self.flight.record(
                'promote',
                rids=[rid],
                blocks=record['blocks'],
                tokens=record['tokens'],
                host_s=record['host_s'],
                put_s=record['put_s'],
                wait_s=round(wait_s, 6),
                span_s=round(span_s, 6),
                overlap=round(max(0.0, 1.0 - wait_s / span_s), 4)
                if span_s > 0 else None,
            )
        if not ready:
            return []
        return self._run_prefill_paged(ready, defer_to)

    def tier_summary(self) -> dict:
        """Host/disk KV-tier counters and promotion-overlap efficiency
        (empty when the tier is disabled)."""
        if self.kv_tier is None:
            return {}
        wait = self._tier_times['promote_wait_s']
        span = self._tier_times['promote_span_s']
        out = {
            'spills': int(self._stats.get('tier_spills', 0)),
            'spilled_blocks': int(self._stats.get('tier_spilled_blocks', 0)),
            'promotions': int(self._stats.get('tier_promotions', 0)),
            'promoted_blocks': int(
                self._stats.get('tier_promoted_blocks', 0)
            ),
            'promote_wait_s': round(wait, 6),
            'promote_span_s': round(span, 6),
            'promotion_overlap': (
                round(max(0.0, 1.0 - wait / span), 4) if span > 0 else None
            ),
            'host_blocks': self.kv_tier.num_blocks,
            'host_bytes': self.kv_tier.bytes_used,
        }
        if self.kv_tier.disk is not None:
            out['disk_blocks'] = self.kv_tier.disk.num_blocks
            out['disk_bytes'] = self.kv_tier.disk.bytes_used
        if self.kv_tier.peer is not None:
            out['peer_fetched_blocks'] = self.kv_tier.peer.fetched_blocks
            out['peer_fetched_bytes'] = self.kv_tier.peer.fetched_bytes
        if self._peer_kv_server is not None:
            out['peer_served_blocks'] = self._peer_kv_server.served_blocks
            out['peer_served_bytes'] = self._peer_kv_server.served_bytes
        return out

    def _prefill_batch_cap(self, bucket: int) -> int:
        """Largest pow2 batch for this bucket under the prefill caps.

        Also bounded by pow2ceil(max_num_seqs): no admission group can
        exceed the slot count, so larger shapes would be compiled (by
        ``warmup``) but never dispatched.
        """
        cap = min(
            self.config.max_prefill_batch,
            max(1, self.config.max_prefill_tokens // bucket),
        )
        b = 1
        while b * 2 <= cap:
            b *= 2
        seqs_ceil = 1
        while seqs_ceil < self.config.max_num_seqs:
            seqs_ceil *= 2
        return min(b, seqs_ceil)

    # ------------------------------------------------ mixed serving windows
    def _mixed_rows(self) -> int:
        """Chunk-row count of every mixed dispatch: the pow2 ceiling of
        ``max_window_prefill_seqs``. FIXED (planner under-fills with trash
        rows) so the row dim never adds compiled shapes — only the chunk
        token bucket varies."""
        b = 1
        while b < self.config.max_window_prefill_seqs:
            b *= 2
        return b

    def _mixed_span_cap(self) -> int:
        """Largest chunk span one request may ride per window: the window
        budget, further capped by ``prefill_chunk_tokens`` when set so
        mixed chunk planning composes with the chunked-prefill buckets."""
        cap = min(
            self.config.max_window_prefill_tokens,
            self.config.max_model_len,
        )
        if self.config.prefill_chunk_tokens:
            cap = min(cap, self.config.prefill_chunk_tokens)
        return max(1, cap)

    @staticmethod
    def _decode_ready(request: Request) -> bool:
        """May this running request take decode steps? False only while
        its prefill tail is still riding mixed windows (the final chunk's
        processed sample is what turns it decode-ready)."""
        return request.prefill_done >= request.prefill_target

    def _mixed_can_ride(self) -> bool:
        """True when windows are flowing for chunks to ride: some running
        request is actively decoding (emitted or in-flight tokens), or
        chunk work is already pending (chunk-only windows keep dispatching
        until it drains). At cold start neither holds and admission uses
        the standalone prefill path — a chunk-only window would pay the
        full ``decode_steps`` weight stream for a handful of prefill
        tokens, so the engine bootstraps the stream before anything rides
        it. Freshly admitted same-batch requests don't count: they have
        neither output nor unacked tokens yet."""
        if self._prefilling:
            return True
        for _, rid in self.sched.running():
            request = self._requests[rid]
            if request.output_ids or self._unacked.get(rid):
                return True
        return False

    def _enroll_mixed(self, request: Request) -> None:
        """Route this admitted request's uncached tail through mixed
        windows. COW resolves here (admission) rather than at prefill
        dispatch — the source block's contents are already final, so the
        copy is value-identical at either point."""
        if request.cow_src_block is not None:
            self._resolve_cow([request])
        request.prefill_target = request.num_tokens
        request.prefill_sent = request.num_cached_tokens
        request.prefill_done = request.num_cached_tokens
        self._prefilling.append(request.request_id)

    def _plan_window_chunks(self) -> list[tuple[Request, int, int]]:
        """Chunk spans riding the next window: FIFO over mid-prefill
        requests, one span each, bounded by the window token budget, the
        row cap, and the span cap. Returns ``[(request, start, ntok)]``
        in absolute tokens; prunes stale (finished/preempted) entries."""
        if self._mixed_window is None or not self._prefilling:
            return []
        budget = self.config.max_window_prefill_tokens
        span_cap = self._mixed_span_cap()
        plan: list[tuple[Request, int, int]] = []
        for rid in list(self._prefilling):
            if budget <= 0 or len(plan) >= self.config.max_window_prefill_seqs:
                break
            request = self._requests.get(rid)
            if request is None or request.state is not RequestState.RUNNING:
                self._prefilling.remove(rid)
                continue
            remaining = request.prefill_target - request.prefill_sent
            if remaining <= 0:
                continue  # final chunk already in flight
            ntok = min(remaining, span_cap, budget)
            plan.append((request, request.prefill_sent, ntok))
            budget -= ntok
        return plan

    def _span_host_arrays(self, spans, bucket: int, rows: int,
                          token_rows=None):
        """The padded paged-span host arrays — (ids, positions,
        block_rows, context_lens, tail_lens) — for ``spans`` =
        ``[(request, start, ntok)]``. ONE builder shared by standalone
        paged prefill, mixed chunk rows, and speculative verify spans:
        the span/padding contract (trash-routed pads, clamped RoPE
        positions) is exactly what the mixed-vs-pure and spec-on/off
        bit-identity guarantees rest on, so it must not be able to
        diverge between the dispatch paths. Pad rows — and spans whose
        ``request`` is None or ``ntok`` 0 (inactive slots in a spec
        window's slot-indexed layout) — carry tail 0 + all-zero tables:
        writes land in the trash block and their logits are garbage the
        caller discards. ``token_rows`` (parallel to ``spans``) supplies
        each span's tokens explicitly instead of slicing the request's
        history — verify spans carry drafts that are not history yet."""
        ids = np.zeros((rows, bucket), np.int32)
        positions = np.zeros((rows, bucket), np.int32)
        block_rows = np.zeros((rows, self.max_blocks_per_seq), np.int32)
        context_lens = np.ones((rows,), np.int32)
        tail_lens = np.zeros((rows,), np.int32)
        max_pos = self.config.max_model_len - 1
        for i, (request, start, ntok) in enumerate(spans):
            if request is None or ntok <= 0:
                continue  # inactive slot: the pad-row contract applies
            toks = (
                token_rows[i][:ntok]
                if token_rows is not None
                else (request.prompt_ids + request.output_ids)[
                    start : start + ntok
                ]
            )
            ids[i, :ntok] = toks
            # Padding columns clamp to max_model_len-1 so the RoPE table
            # gather stays in range; their writes are masked to trash.
            positions[i] = np.minimum(start + np.arange(bucket), max_pos)
            block_rows[i] = self._block_row(request.request_id)
            context_lens[i] = start + ntok
            tail_lens[i] = ntok
        return ids, positions, block_rows, context_lens, tail_lens

    def _build_chunk_arrays(self, chunk_plan) -> list[np.ndarray]:
        """Host arrays for a mixed window's chunk rows, in the mixed
        executable's operand order: the shared span arrays plus per-row
        sampling params. Rows pad to the FIXED ``_mixed_rows()`` count."""
        cb = self._mixed_rows()
        bucket = pick_bucket(
            max(ntok for _, _, ntok in chunk_plan), self.prefill_buckets
        )
        ids, positions, block_rows, context_lens, tail_lens = (
            self._span_host_arrays(chunk_plan, bucket, cb)
        )
        c_temp = np.zeros((cb,), np.float32)
        c_top_p = np.ones((cb,), np.float32)
        c_min_p = np.zeros((cb,), np.float32)
        c_top_k = np.zeros((cb,), np.int32)
        c_seeds = np.zeros((cb,), np.uint32)
        for i, (request, _, _) in enumerate(chunk_plan):
            c_temp[i] = request.params.temperature
            c_top_p[i] = request.params.top_p
            c_min_p[i] = request.params.min_p
            c_top_k[i] = request.params.top_k
            c_seeds[i] = request.sample_seed
        return [ids, positions, block_rows, context_lens, tail_lens,
                c_temp, c_top_p, c_min_p, c_top_k, c_seeds]

    # -------------------------------------------------------------- prefill
    def _mark_prefill_retry(self, requests: list[Request]) -> None:
        """A prefill dispatch for ``requests`` failed: gate each request
        out of decode plans (the mixed-window prefill_target mechanism —
        decode must never read KV the prefill never wrote) and queue it
        for a recovery re-dispatch (``_retry_pending_prefills``). Chunk
        progress resets to the cached prefix: re-writing already-written
        positions is idempotent, so the retry is exact."""
        for request in requests:
            request.prefill_target = max(1, self._prefill_end(request))
            request.prefill_sent = request.num_cached_tokens
            request.prefill_done = request.num_cached_tokens
            if request.request_id not in self._pending_prefill:
                self._pending_prefill.append(request.request_id)

    def _retry_pending_prefills(self, defer_to=None) -> list[tuple[int, int]]:
        """Re-dispatch prefills whose earlier attempt failed (recovery
        path), through the paged route — tail-only over whatever KV is
        already valid, which covers dense-path victims too (their tail is
        the whole prompt)."""
        if not self._pending_prefill:
            return []
        rids, self._pending_prefill = self._pending_prefill, []
        requests: list[Request] = []
        for rid in rids:
            request = self._requests.get(rid)
            if request is None or request.state is not RequestState.RUNNING:
                continue  # quarantined / preempted / finished meanwhile
            request.prefill_target = 0
            request.prefill_sent = request.num_cached_tokens
            request.prefill_done = request.num_cached_tokens
            requests.append(request)
        return self._run_prefill_paged(requests, defer_to)

    def _run_prefill_batch(
        self, requests: list[Request], bucket: int, defer_to=None
    ) -> list[tuple[int, int]]:
        """Dense-path prefill with the recovery contract: a failure marks
        every batched request for re-prefill before propagating, so a
        retrying serving loop cannot decode over unwritten KV (the retry
        routes through the paged path — bit-identical in fp32, while a
        bf16 retry may differ bitwise from the dense kernel; chaos
        identity guarantees are fp32, docs/resilience.md)."""
        try:
            self._faults.fail('dispatch')
            return self._run_prefill_batch_inner(requests, bucket, defer_to)
        except Exception:
            self._mark_prefill_retry(requests)
            raise

    def _run_prefill_batch_inner(
        self, requests: list[Request], bucket: int, defer_to=None
    ) -> list[tuple[int, int]]:
        """Prefill same-bucket requests in one padded dispatch.

        ``defer_to`` (a deque of in-flight window records) switches to the
        pipelined emission path: first tokens stay on device and their
        host fetch is processed later with the decode windows.

        The batch dim pads up the pow2 ladder (capped at
        ``max_prefill_batch``) so the jit cache holds at most
        O(log batch x log length) prefill shapes. Padding rows carry
        length 0: their K/V scatter lands in trash block 0 and their
        sampled token is discarded.
        """
        _metrics.ENGINE_PREFILL_BATCH.observe(len(requests))
        step = self._begin_step()
        step.mark('plan')
        b = 1
        while b < len(requests):
            b *= 2
        ids = np.zeros((b, bucket), np.int32)
        mask = np.zeros((b, bucket), np.int32)
        last_pos = np.zeros((b,), np.int32)
        lengths = np.zeros((b,), np.int32)
        block_rows = np.zeros((b, self.max_blocks_per_seq), np.int32)
        for i, request in enumerate(requests):
            prompt = request.prompt_ids + request.output_ids
            ids[i, : len(prompt)] = prompt
            mask[i, : len(prompt)] = 1
            last_pos[i] = len(prompt) - 1
            lengths[i] = len(prompt)
            block_rows[i] = self._block_row(request.request_id)

        step.mark('put')
        (
            ids_dev,
            mask_dev,
            last_pos_dev,
            block_rows_dev,
            lengths_dev,
        ) = self._put_many(ids, mask, last_pos, block_rows, lengths)
        step.mark('prefill')
        last_logits, k_all, v_all = self._call(
            self._prefill, self.params, ids_dev, mask_dev, last_pos_dev
        )
        self.kv.k_pool, self.kv.v_pool = self._call(
            self._write_prefill,
            self.kv.k_pool,
            self.kv.v_pool,
            k_all,
            v_all,
            block_rows_dev,
            lengths_dev,
        )
        step.mark('emit')
        # Full prompt blocks just entered the paged cache — adopt them
        # into the prefix cache BEFORE emission (a max_tokens=1 request
        # finishes inside _emit_prefill, after which its row is gone).
        for request in requests:
            self._insert_prompt_blocks(request)
        self._note_prefill(
            [(r, int(n)) for r, n in zip(requests, lengths)], 'dense'
        )
        emitted = self._emit_prefill(
            requests, last_logits, b, defer_to, step
        )
        step.close()
        self._note_prefill_seconds(requests, step.t1 - step.t0, step.t0)
        self._record_step(
            'prefill', step, batch=len(requests),
            tokens=int(lengths.sum()), route='dense',
            **self._moe_form_field(b * bucket), **self._rids_field(requests),
        )
        return emitted

    def _emit_prefill(
        self,
        requests: list[Request],
        last_logits,
        b: int,
        defer_to,
        step: _steps.StepSpan,
    ) -> list[tuple[int, int]]:
        """Sample + emit each prefilled request's first token.

        First token of each sequence, sampled from its last prompt
        position; padding rows sample too but are dropped here.
        """
        slots: list[Request | None] = list(requests) + [None] * (
            b - len(requests)
        )
        if defer_to is None:
            # The synchronous path's host sync: the device runs the
            # prefill while the host waits here.
            step.mark('fetch')
            sampled = self._sample_device(last_logits, slots, step)
            self._fetching = {'tokens': sampled}
            tokens = np.asarray(sampled)
            self._fetching = None
            step.mark('emit')
            emitted = []
            for i, request in enumerate(requests):
                token = int(tokens[i])
                self._emit_token(request, token)
                emitted.append((request.request_id, token))
            return emitted

        # Pipelined path: the sampled first tokens STAY on device. They are
        # scattered into the carried last-ids vector (so the next decode
        # window reads them without a host round trip) and the host fetch
        # rides the in-flight deque as a 1-step window record — the same
        # unacked/one-window-late bookkeeping decode EOS already uses.
        tok_dev = self._sample_device(last_logits, slots, step)
        slot_of = {rid: slot for slot, rid in self.sched.running()}
        slot_idx = np.asarray(
            [slot_of[r.request_id] for r in requests], np.int32
        )
        if self._carried is None:
            self._carried = self._put(
                np.zeros((self.config.max_num_seqs,), np.int32)
            )
        self._carried = self._scatter_tokens(
            self._carried, self._put(slot_idx), tok_dev[: len(requests)]
        )
        plan = []
        for i, request in enumerate(requests):
            rid = request.request_id
            self._unacked[rid] = self._unacked.get(rid, 0) + 1
            plan.append((i, rid, 1))
        defer_to.append({'tokens': tok_dev[None, :], 'plan': plan})
        return []

    # ---------------------------------------------- prefix-cached prefill
    def _run_prefill_paged(
        self, requests: list[Request], defer_to=None
    ) -> list[tuple[int, int]]:
        """Prefill requests through the paged-context path: cache hits
        prefill only their uncached tail. Tails of at most
        ``prefill_chunk_tokens`` batch by bucket up to the bucket's cap;
        the longer ones of the round go, together, through
        ``_run_prefill_chunked``."""
        if not requests:
            return []
        self._resolve_cow(
            [r for r in requests if r.cow_src_block is not None]
        )
        emitted: list[tuple[int, int]] = []
        chunk = self.config.prefill_chunk_tokens
        whole: dict[int, list[Request]] = {}
        chunked: list[Request] = []
        for request in requests:
            tail = self._prefill_end(request) - request.num_cached_tokens
            if tail <= 0:
                continue  # under one block: its first window is given it all
            if chunk and tail > chunk:
                chunked.append(request)
            else:
                bucket = pick_bucket(tail, self.prefill_buckets)
                whole.setdefault(bucket, []).append(request)
        for bucket, rs in sorted(whole.items()):
            cap = self._prefill_batch_cap(bucket)
            for i in range(0, len(rs), cap):
                batch = rs[i : i + cap]
                spans = [
                    (
                        r,
                        r.num_cached_tokens,
                        self._prefill_end(r) - r.num_cached_tokens,
                    )
                    for r in batch
                ]
                emitted.extend(
                    self._dispatch_prefill_paged(
                        spans, bucket, defer_to, sample=self._block == 1
                    )
                )
        emitted.extend(self._run_prefill_chunked(chunked, defer_to))
        return emitted

    def _prefill_end(self, request: Request) -> int:
        """The tokens a request's prefill covers: all it has, or, where
        blocks of positions are decided together, the whole blocks of them
        (the rest are the given tokens of its first window's first block)."""
        return request.num_tokens - request.num_tokens % self._block

    def _run_prefill_chunked(
        self, requests: list[Request], defer_to=None
    ) -> list[tuple[int, int]]:
        """Prefill the long uncached tails of one admission round as
        bucketed chunks, in lockstep rounds: round *r* holds chunk *r* of
        every request that still has one.

        Each chunk attends over the KV already in the paged cache (the
        cached prefix plus earlier chunks; a hybrid's chunk starts from
        the state its row's chunk before left in its slot), so splitting
        is exact, and rows of one dispatch share nothing but the weights.
        A round's spans group by ``(bucket, final)``: only a final chunk
        samples. **A group dispatches together only when it is full**:
        ``_prefill_batch_cap(bucket)`` spans go as one dispatch, which
        reads the weights once for all of them; what is left after the
        full groups goes one span a dispatch. So a chunk dispatch has
        ``cap`` rows or one and no other count: ``(bucket, cap)`` is a
        program ``warmup()`` lists and whole tails already run, while a
        partial group's ``(bucket, 2)`` could be a program no warm-up
        met, compiled inside a request's wait for its first token. The
        remainder goes before the full groups, so that the round's first
        dispatch is the one-row program whenever there is a remainder: a
        lone long prompt in a served stream sends that program right
        after a decode window, and a program that takes the KV pools is
        lowered once for each commitment of them (``PERF.md`` section 6,
        PR 24), so a warm-up call of several long prompts has to meet it
        in that place too.

        After every dispatch that is not final the pipelined loop may
        retire an in-flight decode window (``_drain_hook``) so long
        prompts cannot stall decode for their whole prefill. A failed
        dispatch marks its own requests for retry
        (``_dispatch_prefill_paged``); the others of the round that still
        have a chunk to go are marked here, since their KV is part
        written and nothing else would come back for them.
        """
        chunk = self.config.prefill_chunk_tokens
        emitted: list[tuple[int, int]] = []
        # Where each request's next chunk starts; gone once its final
        # chunk has been dispatched.
        pending = {r.request_id: (r, r.num_cached_tokens) for r in requests}
        try:
            while pending:
                groups: dict[tuple[int, bool], list] = {}
                for request, start in pending.values():
                    ntok = min(chunk, self._prefill_end(request) - start)
                    final = start + ntok >= self._prefill_end(request)
                    bucket = pick_bucket(ntok, self.prefill_buckets)
                    groups.setdefault((bucket, final), []).append(
                        (request, start, ntok)
                    )
                batches: list[tuple[int, bool, list]] = []
                for bucket, final in sorted(groups):
                    spans = groups[bucket, final]
                    cap = self._prefill_batch_cap(bucket)
                    # The remainder goes first: see the docstring.
                    rest = len(spans) % cap
                    batches += [(bucket, final, [s]) for s in spans[:rest]]
                    batches += [
                        (bucket, final, spans[i : i + cap])
                        for i in range(rest, len(spans), cap)
                    ]
                for bucket, final, batch in batches:
                    self._stats['prefill_chunks'] += len(batch)
                    self._stats['prefill_chunk_groups'] += len(batch) > 1
                    _metrics.ENGINE_PREFILL_CHUNKS.inc(len(batch))
                    for _, _, ntok in batch:
                        _metrics.ENGINE_PREFILL_CHUNK_TOKENS.observe(ntok)
                    emitted.extend(
                        self._dispatch_prefill_paged(
                            batch, bucket, defer_to,
                            sample=final and self._block == 1, route='chunk',
                        )
                    )
                    for request, start, ntok in batch:
                        if final:
                            del pending[request.request_id]
                        else:
                            pending[request.request_id] = (
                                request, start + ntok,
                            )
                    if not final and self._drain_hook is not None:
                        self._drain_hook()
        except Exception:
            self._mark_prefill_retry([r for r, _ in pending.values()])
            raise
        return emitted

    def _dispatch_prefill_paged(
        self,
        spans: list[tuple[Request, int, int]],
        bucket: int,
        defer_to=None,
        sample: bool = True,
        route: str = 'paged',
    ) -> list[tuple[int, int]]:
        """Paged-path prefill with the recovery contract (see
        ``_run_prefill_batch``): mark-for-retry on failure, then raise."""
        try:
            self._faults.fail('dispatch')
            return self._dispatch_prefill_paged_inner(
                spans, bucket, defer_to, sample, route
            )
        except Exception:
            self._mark_prefill_retry([r for r, _, _ in spans])
            raise

    def _dispatch_prefill_paged_inner(
        self,
        spans: list[tuple[Request, int, int]],
        bucket: int,
        defer_to=None,
        sample: bool = True,
        route: str = 'paged',
    ) -> list[tuple[int, int]]:
        """One padded paged-context prefill dispatch.

        ``spans`` is ``[(request, start_token, num_tokens)]``; every span's
        K/V lands in the request's own blocks at absolute positions, and
        its queries attend to everything before them through the paged
        cache. ``sample=False`` (intermediate chunks) skips emission.
        ``route`` names the record's route: ``paged`` (a tail behind
        cached blocks) or ``chunk`` (one chunk of a long tail).
        """
        requests = [r for r, _, _ in spans]
        _metrics.ENGINE_PREFILL_BATCH.observe(len(requests))
        self._stats['prefill_dispatches'] += 1
        _metrics.ENGINE_PREFILL_DISPATCHES.inc()
        step = self._begin_step()
        step.mark('plan')
        b = 1
        while b < len(spans):
            b *= 2
        ids, positions, block_rows, context_lens, tail_lens = (
            self._span_host_arrays(spans, bucket, b)
        )
        host_arrays = [ids, positions, block_rows, context_lens, tail_lens]
        window_fields: dict = {}
        if self.window_blocks is not None:
            window_rows, window_fields = self._window_span_tables(spans, b)
            host_arrays.insert(3, window_rows)
        if self.state_pool is not None:
            # Each row's slot of the state pool: the scheduler's slot of
            # its sequence; a pad row's lies past the pool.
            slot_of = {rid: slot for slot, rid in self.sched.running()}
            slots = np.full((b,), self.state_pool.slots, np.int32)
            slots[: len(requests)] = [slot_of[r.request_id] for r in requests]
            host_arrays.append(slots)
        step.mark('put')
        devs = list(self._put_many(*host_arrays))
        step.mark('prefill')
        if window_fields:
            devs[2:4] = [(devs[2], devs[3])]  # the two groups' tables
        last_logits = self._call_prefill_paged(*devs)
        if window_fields:
            # Issued: what the rows' next queries no longer see goes back.
            window_fields['window_blocks_freed'] += sum(
                self.window_blocks.trim_behind(r.request_id, start + ntok)
                for r, start, ntok in spans
            )
        step.mark('emit')
        self._note_prefill([(r, ntok) for r, _, ntok in spans], route)
        emitted: list[tuple[int, int]] = []
        if sample:
            for request in requests:
                self._insert_prompt_blocks(request)
            emitted = self._emit_prefill(
                requests, last_logits, b, defer_to, step
            )
        step.close()
        self._note_prefill_seconds(requests, step.t1 - step.t0, step.t0)
        kv_blocks = self._kv_blocks(context_lens)
        if window_fields:
            window_fields['kv_blocks_full'] = kv_blocks
        self._record_step(
            'prefill', step, batch=len(requests),
            tokens=int(tail_lens.sum()), route=route, kv_blocks=kv_blocks,
            **window_fields, **self._moe_form_field(b * bucket),
            **self._loop_fields, **self._rids_field(requests),
        )
        return emitted

    def _window_span_tables(self, spans, rows: int):
        """The windowed group's side of a paged prefill dispatch: each
        span's sequence made to hold what the span's queries read and
        write (``WindowBlocks.cover``), the table rows, and the record's
        fields for the group."""
        blocks = self.window_blocks
        tables = np.zeros((rows, self.max_blocks_per_seq), np.int32)
        freed = live = under = 0
        for i, (request, start, ntok) in enumerate(spans):
            if request is None or ntok <= 0:
                continue
            live += 1
            under += start + ntok <= blocks.window
            freed += blocks.cover(request.request_id, start, start + ntok)
            blocks.table_row(request.request_id, tables[i])
        return tables, self._window_fields(tables, live, freed, under)

    def _window_fields(
        self, tables: np.ndarray, live: int, freed: int, under: int
    ) -> dict:
        """``kv_blocks_window``: the windowed group's blocks a dispatch's
        rows hold, a row without a sequence counted for the trash block it
        reads, as ``kv_blocks`` counts it; ``kv_window_pool_blocks``: the
        group's pool, less the trash block; ``rows_under_window``: the live
        rows whose context is no longer than the window (their window
        layers read all of it)."""
        return {
            'kv_blocks_window': int(np.count_nonzero(tables))
            + tables.shape[0] - live,
            'window_blocks_freed': freed,
            'kv_window_pool_blocks': self.window_blocks.num_blocks - 1,
            'rows_under_window': int(under),
        }

    def _release_window_blocks(self, rid: int) -> None:
        """With the scheduler's finish and preemption: the sequence's
        blocks of the windowed group go back too."""
        if self.window_blocks is not None:
            self.window_blocks.release(rid)

    def _resolve_cow(self, requests: list[Request]) -> None:
        """Copy-on-write for aligned full-cover hits: duplicate each
        shared final block into the request's first OWNED block (one
        batched device copy across all layers), so the last prompt
        token's K/V write cannot touch a block other requests read."""
        if not requests:
            return
        srcs: list[int] = []
        dsts: list[int] = []
        for request in requests:
            row = self.sched.block_row(request.request_id)
            dsts.append(row[request.num_borrowed_blocks])
            srcs.append(request.cow_src_block)
            request.cow_src_block = None
        self._stats['prefix_cow_copies'] += len(srcs)
        _metrics.PREFIX_COW_COPIES.inc(len(srcs))
        src_dev, dst_dev = self._put_many(
            np.asarray(srcs, np.int32), np.asarray(dsts, np.int32)
        )
        self.kv.k_pool, self.kv.v_pool = self._cow_copy(
            self.kv.k_pool, self.kv.v_pool, src_dev, dst_dev
        )

    def _insert_prompt_blocks(self, request: Request) -> None:
        """Adopt this request's freshly prefilled FULL prompt blocks into
        the prefix cache (first writer wins) and mark them borrowed in the
        scheduler so finish/preemption cannot free them."""
        if self.prefix_cache is None or not request.digests:
            return
        rid = request.request_id
        row = self.sched.block_row(rid)
        nb = request.num_borrowed_blocks
        lent = nb
        for i in range(nb, len(request.digests)):
            if not self.prefix_cache.insert(rid, request.digests[i], row[i]):
                break
            lent = i + 1
        if lent > nb:
            self.sched.lend_prefix(rid, lent)
            request.num_borrowed_blocks = lent

    def _begin_step(self) -> _steps.StepSpan:
        """The span of a new step (observability/steps.py): its ``mark``
        calls are the ONE set of clock reads behind both the flight
        record's split (``host_s``, ``put_s``, ``dispatch_s``, ``fetch_s``,
        ``emit_s``, ``admit_s``) and the ``distllm:<span>`` annotations of
        a profiler capture. Attribution off keeps the reads and opens no
        annotation."""
        return _steps.StepSpan(next(self._step_seq), self.attribution)

    def _begin_root(self) -> _steps.StepSpan:
        """The root of the span tree, ``distllm:serve``, open over one
        ``step()`` call or one pass of the pipelined loop: an idle gap of
        the device that runs through several phases, none of which holds
        half of it, still falls under an engine span."""
        self._serve_thread = threading.get_ident()
        root = self._begin_step()
        root.mark('serve')
        return root

    def stall_context(self) -> dict:
        """What a stalled stretch's evidence asks of this engine, from the
        watcher's thread (``observability/flight.py`` ``stall_evidence``):
        the ``thread`` that serves it, the windows ``in_flight`` and
        whether each one's tokens are ``ready`` on the device (ready and
        still being waited for is the transfer or the host; not ready is
        the program or the runtime), the ``unfinished`` requests, and
        whether anything is ``compiling``. Plain reads, no lock: a count a
        window off is as good."""
        tokens = [
            window.get('tokens')
            for window in (self._fetching, *self._inflight)
            if window is not None
        ]
        ready = [bool(t.is_ready()) for t in tokens if hasattr(t, 'is_ready')]
        return {
            'thread': self._serve_thread,
            'in_flight': len(ready),
            'ready': ready,
            'unfinished': len(self._requests),
            'compiling': self._compile_watcher.compiling(),
        }

    @contextlib.contextmanager
    def _span(self, name: str):
        """A lone ``distllm:<name>`` span outside any step record (tier
        spill fetch, promotion scatter and its completion sync)."""
        step = self._begin_step()
        step.mark(name)
        try:
            yield step
        finally:
            step.close()

    def _kv_blocks(self, *context_lens: np.ndarray) -> int:
        """KV blocks one step of the paged kernel reads for a dispatch's
        rows (its ``context_lens`` host arrays): the blocks that hold each
        row's context (a pad row has a context of one token and reads the
        trash block). Worked out when the step's record is written, after
        its spans have closed."""
        bs = self.config.block_size
        return sum(int(((c + (bs - 1)) // bs).sum()) for c in context_lens)

    def _group_field(self, stem: str, group) -> str:
        """A step record's field of one cache group: ``stem`` where a model
        has one block table, ``<stem>_full`` / ``<stem>_window`` with two."""
        if len(self.cache_spec.paged) == 1:
            return stem
        return f'{stem}_window' if group.window else f'{stem}_full'

    def _kv_chunks(self, context_lens: np.ndarray) -> dict:
        """``kv_chunks*``: the chunks the paged kernel's row walk fetches
        for a decode dispatch's rows in one step of one layer of each cache
        group: from the chunk that holds a row's sliding-window floor to the
        one that holds its context's end (a pad row's one token is one
        chunk). Against ``rows x ceil(table blocks / pages a chunk)`` it is
        the share of the grid over chunks that fetched anything. Nothing
        under a backend that has no walk. Reckoned where ``kv_blocks`` is,
        after the step's spans have closed."""
        if not self._walk_keys:
            return {}
        fields = {}
        groups = self.cache_spec.paged
        for group in groups:
            keys = self._walk_keys[group.name]
            floor = 0
            if group.window is not None:
                floor = np.maximum(context_lens - group.window, 0)
            fields[self._group_field('kv_chunks', group)] = int(
                ((context_lens + keys - 1) // keys - floor // keys).sum()
            )
        if len(groups) > 1:
            fields['kv_chunks'] = fields['kv_chunks_full']
        return fields

    def _rids_field(self, requests: list[Request]) -> dict:
        if not self.attribution:
            return {}
        return {'rids': [r.request_id for r in requests]}

    @staticmethod
    def _note_prefill(pairs: list[tuple[Request, int]], route: str) -> None:
        """Count one prefill dispatch on each request that rides it
        (before emission: a request may finish at its first token)."""
        for request, ntok in pairs:
            request.prefill_tokens += ntok
            request.routes[route] = request.routes.get(route, 0) + 1

    @staticmethod
    def _note_prefill_seconds(requests: list[Request], seconds: float,
                              t0: float) -> None:
        """Charge a closed prefill step (or a window that carried chunk
        rows) to those of its requests still without a first token
        (``prefill_first_s``): a re-prefill after preemption is not part
        of the wait for the first token."""
        for request in requests:
            if not request.t_first_token or request.t_first_token >= t0:
                request.prefill_first_s += seconds

    @staticmethod
    def _call(fn, *args):
        """Run one of the engine's jit entry points, leaving its function
        and arguments where the compile watcher finds them: a compile
        that fires inside the call records the arguments' signature
        (shape, dtype, weak type, committed, sharding, layout). A call
        that compiles nothing pays for one thread-local store."""
        _steps.set_call(fn, args)
        try:
            return fn(*args)
        finally:
            _steps.set_call(None, None)

    def _sched_gauges(self) -> dict:
        """Scheduler state stamped on a step record."""
        usable = self.config.num_blocks - 1  # block 0 is reserved
        return {
            'queue_depth': self.sched.num_waiting,
            'running': self.sched.num_running,
            'kv_occupancy': round(
                (usable - self.sched.num_free_blocks) / usable, 4
            ) if usable > 0 else 0.0,
        }

    def _record_step(self, kind: str, step: _steps.StepSpan, *, batch: int,
                     tokens: int, duration_s: float | None = None,
                     gauges: dict | None = None, **extra) -> None:
        """One flight-ring record + metrics pair per engine step.

        ``duration_s`` for prefill is the whole step (plan to the end of
        emission); for decode/mixed/spec the caller passes dispatch ->
        host fetch, so pipelined in-flight time is included — the wall
        clock a stalled window would actually burn. ``extra`` carries
        kind-specific fields (the ``mixed`` kind adds
        prefill_tokens/prefill_rows). With attribution on, the closed
        ``step`` adds ``seq``, ``t0_s``/``t1_s`` and the seconds of its
        child spans (docs/observability.md "Serving-path spans").

        With attribution on, the analytic roofline prices the step
        (observability/roofline.py) and the record carries ``mfu`` /
        ``bw_util`` next to the raw fields, mirrored into the
        ``distllm_engine_mfu`` / ``distllm_engine_bandwidth_utilization``
        gauges and the per-kind ``roofline_summary()`` accumulators.
        """
        if duration_s is None:
            duration_s = step.t1 - step.t0
        if self.attribution:
            extra = {**extra, **step.fields()}
        _metrics.ENGINE_STEPS.labels(kind=kind).inc()
        _metrics.ENGINE_STEP_SECONDS.labels(kind=kind).observe(duration_s)
        # EWMA-measured TTFT-predictor inputs (resilience/admission.py),
        # fed regardless of the attribution flag — admission control must
        # keep predicting while attribution is flipped off.
        if kind == 'prefill' and tokens > 0:
            self._ewma_update('prefill_s_per_token', duration_s / tokens)
        else:
            self._ewma_update('window_s', duration_s)
        if self._cost_model is not None and self.attribution:
            cost = self._cost_model.step_cost(
                kind,
                tokens=tokens,
                batch=batch,
                draft_tokens=extra.get('draft_tokens', 0),
                prefill_tokens=extra.get('prefill_tokens', 0),
                **self._block_cost_fields(kind, extra),
            )
            if cost is not None:
                mfu, bw_util = self._cost_model.utilization(cost, duration_s)
                _metrics.ENGINE_MFU.labels(kind=kind).set(mfu)
                _metrics.ENGINE_BW_UTIL.labels(kind=kind).set(bw_util)
                acc = self._roofline.setdefault(
                    kind,
                    {'windows': 0.0, 'seconds': 0.0, 'flops': 0.0,
                     'hbm_bytes': 0.0},
                )
                acc['windows'] += 1
                acc['seconds'] += duration_s
                acc['flops'] += cost.flops
                acc['hbm_bytes'] += cost.hbm_bytes
                extra = {
                    **extra,
                    'mfu': _round_sig(mfu),
                    'bw_util': _round_sig(bw_util),
                }
                # Measured twin (observability/xla_cost.py): the same
                # window priced from what XLA actually compiled, plus the
                # analytic-vs-measured calibration ratio gauges. Published
                # ONLY for dispatches whose compiled shape is the priced
                # one: decode always (fixed b x steps), spec when no
                # chunk rows rode (the chunk-carrying dispatch is a
                # different executable per bucket). Prefill/mixed dispatch
                # at varying (batch, bucket) shapes, so publishing the
                # priced largest-shape cost over a smaller dispatch's
                # wall time would inflate the gauges by the shape ratio —
                # their executable costs stay visible via
                # measured_costs(), never as per-dispatch gauges.
                fixed_shape = kind == 'decode' or (
                    kind == 'spec' and not extra.get('prefill_tokens')
                )
                measured = (
                    self._measured_costs.get(kind) if fixed_shape else None
                )
                if measured is not None:
                    m_mfu, m_bw = _xla_cost.publish_measured(
                        kind, measured, duration_s,
                        self._cost_model.peak_flops,
                        self._cost_model.peak_hbm_bytes,
                    )
                    _xla_cost.record_calibration(
                        kind, cost.flops, cost.hbm_bytes, measured
                    )
                    extra = {
                        **extra,
                        'mfu_measured': _round_sig(m_mfu),
                        'bw_util_measured': _round_sig(m_bw),
                    }
        self.flight.record(
            kind,
            duration_s=round(duration_s, 6),
            batch=batch,
            occupancy=round(batch / self.config.max_num_seqs, 4),
            tokens=tokens,
            **(gauges if gauges is not None else self._sched_gauges()),
            **extra,
        )

    def _block_cost_fields(self, kind: str, extra: dict) -> dict:
        """What a decode window of a model that decides blocks of positions
        costs, from its forwards and positions and not from its tokens: the
        weight passes its program ran (``S + 1`` a block of the window) and
        the positions its live rows' forwards computed."""
        if self._block == 1 or kind != 'decode' or 'forwards' not in extra:
            return {}
        blocks = self.config.decode_steps // self._block
        return {
            'weight_passes': blocks * (self._denoise_steps + 1),
            'positions': extra['forwards'] * self._block,
        }

    def roofline_snapshot(self) -> dict[str, dict[str, float]]:
        """Copy of the raw per-kind roofline accumulators — pass a prior
        snapshot to ``roofline_summary(baseline=...)`` to scope the
        summary to just the windows recorded in between (how the loadgen
        isolates its run from warmup traffic)."""
        return {kind: dict(acc) for kind, acc in self._roofline.items()}

    def roofline_summary(
        self, baseline: dict[str, dict[str, float]] | None = None
    ) -> dict[str, dict[str, float]]:
        """Aggregate roofline view per window kind:
        ``{kind: {windows, seconds, mfu, bw_util}}`` with mfu/bw_util the
        time-weighted means (total flops/bytes over total seconds over
        the device peaks).
        ``baseline`` (a prior :meth:`roofline_snapshot`) subtracts
        earlier windows so the summary covers one measured interval.
        Empty when the cost model was unavailable (and nothing
        accumulates while attribution is off)."""
        if self._cost_model is None:
            return {}
        out: dict[str, dict[str, float]] = {}
        for kind, acc in self._roofline.items():
            base = (baseline or {}).get(kind, {})
            acc = {
                key: value - base.get(key, 0.0)
                for key, value in acc.items()
            }
            seconds = acc['seconds']
            if seconds <= 0:
                continue
            out[kind] = {
                'windows': int(acc['windows']),
                'seconds': round(seconds, 4),
                'mfu': _round_sig(
                    acc['flops'] / seconds / self._cost_model.peak_flops
                ),
                'bw_util': _round_sig(
                    acc['hbm_bytes']
                    / seconds
                    / self._cost_model.peak_hbm_bytes
                ),
            }
        return out

    def _block_row(self, rid: int) -> np.ndarray:
        row = np.zeros((self.max_blocks_per_seq,), np.int32)
        blocks = self.sched.block_row(rid)
        # Window reservation (batch-max kmax, up to pipeline_depth x
        # decode_steps tokens) may overshoot max_model_len by a few blocks;
        # those blocks are never addressed (positions stay < max_model_len)
        # so the row safely truncates.
        n = min(len(blocks), self.max_blocks_per_seq)
        row[:n] = blocks[:n]
        return row

    # --------------------------------------------------------------- decode
    def step(self) -> list[tuple[int, int]]:
        """One synchronous engine iteration: admit, then generate a window
        of up to ``decode_steps`` tokens per running sequence.

        Returns [(request_id, new_token)] in emission order. ``generate_ids``
        does NOT call this — it runs the pipelined loop that keeps
        ``pipeline_depth`` windows in flight; ``step`` is the simple API for
        interactive callers (chat server streaming, tests).

        Crash-domain recovery (``max_dispatch_retries > 0``,
        docs/resilience.md) applies here like in the pipelined loop: a
        failed dispatch is charged, backed off, and retried on the NEXT
        step() call instead of propagating; a step that failed mid-admit
        may under-report tokens already folded into request state, so
        resilient callers (run_loadgen) reconcile from the finished
        requests' ``output_ids``.
        """
        emitted: list[tuple[int, int]] = []
        root = self._begin_root()
        try:
            self._expire_deadlines()
            # The iteration's span: admission, then the window it plans,
            # dispatches, fetches and emits (prefill dispatches inside
            # admission are steps of their own).
            span = self._begin_step()
            span.mark('admit')
            emitted = self._admit()
            if self.sched.num_running == 0:
                span.close()
                return emitted
            window = self._dispatch_window(None, span)
            if window is _DRAIN:
                span.close()
            else:
                emitted.extend(self._process_window(window))
            return emitted
        except Exception as exc:
            _steps.abandon()
            # A sync step has no in-flight deque: whatever window the
            # failed step dispatched is lost with its device-side tokens.
            # Clear the unacked lag and roll chunk progress back (the
            # pipelined loop's abnormal-drain rule) so a recovery retry
            # replans from host-visible state instead of waiting forever
            # on tokens nothing will ever fetch.
            self._unacked.clear()
            for pending_rid in self._prefilling:
                pending = self._requests.get(pending_rid)
                if pending is not None:
                    pending.prefill_sent = pending.prefill_done
            if not self._recover(exc):
                raise
            return emitted
        finally:
            self._fetching = None
            root.close()

    def _window_budget(self, request: Request, unacked: int, k: int) -> int:
        """Tokens this request may still generate in a new window, after
        accounting for unfetched device-side tokens. Zero while the
        request's prefill tail is still riding mixed windows."""
        if not self._decode_ready(request):
            return 0
        if self._block > 1:
            return self._block_cover(request, unacked, k)[1]
        return max(0, min(k, self._budget_left(request) - unacked))

    def _block_cover(
        self, request: Request, unacked: int, k: int
    ) -> tuple[int, int, int]:
        """For a model that decides blocks of positions together:
        ``(positions the request's next window covers, tokens it emits,
        given tokens of its first block)``. A window covers whole blocks
        from the last whole block the request's tokens fill; the tokens
        past that one (a prompt's remainder, or what a preempted request
        had decided of its last block) are given in the first block, and
        the last block of a budget is decided whole, then cut."""
        block = self._block
        room = self._budget_left(request) - unacked
        if room <= 0 or not self._decode_ready(request):
            return 0, 0, 0
        given = (request.num_tokens + unacked) % block
        covered = block * min(k // block, -(-(room + given) // block))
        return covered, min(covered - given, room), given

    def _budget_left(self, request: Request) -> int:
        """Tokens the request may still emit (those in flight included)
        before ``max_tokens`` or ``max_model_len`` ends it."""
        return min(
            request.params.max_tokens - len(request.output_ids),
            self.config.max_model_len - request.num_tokens,
        )

    def _window_reserve(self) -> dict[int, int]:
        """Per decode-ready running row, the tokens beyond ``num_tokens``
        its blocks must cover once the next window has run: what is in
        flight (unacked) plus this window's steps, capped by the row's
        own budget (``decode_loop`` routes the writes of a row past its
        budget to the trash block). A row reserves for itself, never for
        the batch's largest window: the decode-budget walk
        (scheduler.py) counts on a row never holding more than its own
        end needs."""
        k = self.config.decode_steps
        reserve = {}
        for _, rid in self.sched.running():
            request = self._requests[rid]
            if not self._decode_ready(request):
                # Mixed prefill rows, promotion-pending rows and rows
                # whose failed prefill awaits its retry take no decode
                # steps this window, and their blocks were granted at
                # admission: extending them would allocate (and possibly
                # preempt) for rows that write nothing.
                continue
            unacked = self._unacked.get(rid, 0)
            if self._block > 1:
                # to the end of the last block the window decides
                covered, _, given = self._block_cover(request, unacked, k)
                reserve[rid] = max(1, unacked - given + covered)
                continue
            reserve[rid] = max(
                1, unacked + self._window_budget(request, unacked, k)
            )
        return reserve

    def _reserve_shortfall(self, row_ks: dict[int, int]) -> int:
        """Blocks ``prepare_decode`` would need beyond what the rows of
        ``row_ks`` (rid -> headroom in tokens) already own — used by the
        pipelined loop to guarantee no preemption happens while windows
        are in flight (preempting a sequence whose blocks an in-flight
        window still writes to would let a re-allocation corrupt another
        sequence's KV). Running rows absent from ``row_ks`` take no
        decode extension this window."""
        bs = self.config.block_size
        short = 0
        for rid, k_row in row_ks.items():
            target = -(-(self._requests[rid].num_tokens + k_row) // bs)
            short += max(0, target - len(self.sched.block_row(rid)))
        return short

    def _dispatch_window(
        self, carried_ids, step: _steps.StepSpan
    ) -> dict | object:
        """Plan and dispatch one fused decode window (no host sync).

        ``carried_ids`` is the previous window's device-side last-token
        vector (None = build fully from host knowledge). Slots with no
        unacked tokens are overridden from host state — fresh admissions,
        reused slots, or a drained pipeline. Under mixed batching the
        window may additionally carry prefill-chunk rows (planned below)
        and dispatch through the fused mixed executable. Returns the
        in-flight window record, or ``_DRAIN`` when every running slot's
        budget is already covered by in-flight windows AND no chunk work
        is pending (caller should process one). ``step`` is the loop
        iteration's span, open since admission: planning starts its
        ``distllm:plan`` child here, and the window record carries it to
        ``_process_window``.

        ``draft_k > 0`` routes to the speculative verify window instead
        (docs/speculative.md): one ragged dispatch scoring every row's
        prompt-lookup draft span. Spec windows ignore ``carried_ids`` —
        they process synchronously, so host state is always current.
        """
        if self.config.draft_k:
            return self._dispatch_spec_window(step)
        # Injection site 'dispatch' (docs/resilience.md): fires BEFORE
        # any state mutation (key split, unacked counts, chunk progress),
        # so a recovery retry replans from unchanged state — the
        # simulation boundary for an XLA dispatch raise.
        self._faults.fail('dispatch')
        step.mark('plan')
        k = self.config.decode_steps
        row_ks = self._window_reserve()
        if row_ks:
            # Eviction pressure beats preemption: unreferenced cached
            # blocks are free capacity, so spend those before recompute-
            # preempting a running sequence.
            short = (
                self._reserve_shortfall(row_ks) - self.sched.num_free_blocks
            )
            short -= self._evict_cached_blocks(short)
            if self._faults.fire('sched_exhausted') is not None:
                # Injection site 'sched_exhausted': the pool-pressure
                # hazard, without needing a pool actually sized to hit it.
                self._budget_use_was_low()
                raise SchedulerExhausted(
                    'injected scheduler exhaustion', preempted=[]
                )
            # The pipelined loop drains in-flight windows before any
            # dispatch that could preempt, so victims never have unacked
            # device-side tokens OR in-flight chunk writes; recompute
            # preemption re-prefills them.
            self._prepare_decode(
                step, short, max(row_ks.values()), list(row_ks),
                list(row_ks.values()),
            )
        # A chunk-only window (no decode-ready rows) skips prepare_decode
        # entirely: chunk writes land in admission-granted blocks, so it
        # must neither allocate nor preempt. Planned AFTER preemption so
        # a preempted victim's span never rides this window.
        chunk_plan = self._plan_window_chunks()
        running = [
            (slot, self._requests[rid]) for slot, rid in self.sched.running()
        ]
        if not running:
            return _DRAIN

        b = self.config.max_num_seqs
        ids = np.zeros(self._ids_shape(b), np.int32)
        positions = np.zeros((b,), np.int32)
        context_lens = np.ones((b,), np.int32)
        block_tables = np.zeros((b, self.max_blocks_per_seq), np.int32)
        steps_left = np.zeros((b,), np.int32)
        # Blocks of positions: a row's threshold (1.0: the static rule), and
        # the given tokens its first block starts with, by rid.
        thresholds = np.ones((b,), np.float32)
        given_of: dict[int, int] = {}
        temperature = np.zeros((b,), np.float32)
        top_p = np.ones((b,), np.float32)
        min_p = np.zeros((b,), np.float32)
        top_k = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.uint32)
        override_mask = np.zeros((b,), bool)
        window_blocks = self.window_blocks
        if window_blocks is not None:
            window_tables = np.zeros_like(block_tables)
            window_freed = window_live = window_under = 0
        plan: list[tuple[int, int, int]] = []
        any_steps = False
        for slot, request in running:
            rid = request.request_id
            unacked = self._unacked.get(rid, 0)
            if self._block > 1:
                # The window covers whole blocks (``steps_left``); what it
                # emits (``steps``) is less by what its first block is given.
                covered, steps, given = self._block_cover(request, unacked, k)
            else:
                steps = self._window_budget(request, unacked, k)
            total = request.num_tokens + unacked
            positions[slot] = total - 1
            context_lens[slot] = total
            block_tables[slot] = self._block_row(rid)
            if window_blocks is not None and steps:
                # The window's queries sit at total - 1 onward, one a step.
                window_live += 1
                window_under += total <= window_blocks.window
                window_freed += window_blocks.cover(
                    rid, total - 1, total - 1 + steps
                )
                window_blocks.table_row(rid, window_tables[slot])
            steps_left[slot] = covered if self._block > 1 else steps
            temperature[slot] = request.params.temperature
            top_p[slot] = request.params.top_p
            min_p[slot] = request.params.min_p
            top_k[slot] = request.params.top_k
            seeds[slot] = request.sample_seed
            if self._block > 1:
                if given and steps:
                    given_of[rid] = given
                    ids[slot, :given] = (
                        request.prompt_ids + request.output_ids
                    )[-given:]
                if request.params.unmask_threshold is not None:
                    thresholds[slot] = request.params.unmask_threshold
            elif unacked == 0:
                ids[slot] = (
                    request.output_ids[-1]
                    if request.output_ids
                    else request.prompt_ids[-1]
                )
                override_mask[slot] = True
            plan.append((slot, rid, steps))
            any_steps = any_steps or steps > 0
        if not any_steps and not chunk_plan:
            return _DRAIN
        step.counts['sampled_rows'] = _sampled_rows(temperature)

        host_arrays = [
            ids, override_mask, positions, context_lens, block_tables,
            steps_left, temperature, top_p, min_p, top_k, seeds,
        ]
        context_arrays = [context_lens]
        window_fields: dict = {}
        if window_blocks is not None:
            host_arrays.append(window_tables)  # never beside a chunk plan
            window_fields = self._window_fields(
                window_tables, window_live, window_freed, window_under
            )
        if self._block > 1:
            host_arrays.append(thresholds)  # never beside either
        if chunk_plan:
            chunk_arrays = self._build_chunk_arrays(chunk_plan)
            context_arrays.append(chunk_arrays[3])
            host_arrays.extend(chunk_arrays)
        step.mark('put')
        devs = self._put_many(*host_arrays)
        step.mark('mixed' if chunk_plan else 'decode')
        (
            ids_dev,
            override_dev,
            positions_dev,
            context_lens_dev,
            block_tables_dev,
            steps_left_dev,
            temperature_dev,
            top_p_dev,
            min_p_dev,
            top_k_dev,
            seeds_dev,
        ) = devs[:11]
        if window_blocks is not None:
            block_tables_dev = (block_tables_dev, devs[11])
        if carried_ids is not None and self._block == 1:
            # (a block starts masked: nothing of the window before is read)
            ids_dev = self._merge_ids(carried_ids, override_dev, ids_dev)
        chunk_tokens = None
        moe_pairs = None
        chunk_entries: list[tuple[int, int, int, int, bool]] = []
        if chunk_plan:
            (
                tokens,
                self.kv.k_pool,
                self.kv.v_pool,
                last_ids,
                chunk_tokens,
            ) = self._call(
                self._mixed_window,
                self.params,
                ids_dev,
                positions_dev,
                context_lens_dev,
                self.kv.k_pool,
                self.kv.v_pool,
                block_tables_dev,
                steps_left_dev,
                temperature_dev,
                top_p_dev,
                min_p_dev,
                top_k_dev,
                seeds_dev,
                *devs[11:],
            )
            ridden = 0
            for i, (request, start, ntok) in enumerate(chunk_plan):
                request.prefill_sent = start + ntok
                final = start + ntok >= request.prefill_target
                chunk_entries.append(
                    (i, request.request_id, start, ntok, final)
                )
                ridden += ntok
            self._stats['mixed_windows'] += 1
            self._stats['mixed_prefill_tokens'] += ridden
            _metrics.MIXED_WINDOWS.inc()
            _metrics.MIXED_PREFILL_TOKENS.inc(ridden)
            _metrics.MIXED_PREFILL_TOKENS_PER_WINDOW.observe(ridden)
            _metrics.MIXED_PREFILL_ROWS.observe(len(chunk_plan))
        else:
            tokens, last_ids, moe_pairs = self._call_decode_window(
                ids_dev,
                positions_dev,
                context_lens_dev,
                block_tables_dev,
                steps_left_dev,
                temperature_dev,
                top_p_dev,
                min_p_dev,
                top_k_dev,
                seeds_dev,
                *(devs[11:12] if self._block > 1 else ()),  # the thresholds
            )
        for _, rid, steps in plan:
            if steps:
                self._unacked[rid] = self._unacked.get(rid, 0) + steps
        self._stats['decode_windows'] += 1
        _metrics.ENGINE_DECODE_WINDOWS.inc()
        _metrics.ENGINE_DECODE_UTILIZATION.observe(
            sum(1 for _, _, steps in plan if steps > 0) / b
        )
        # The window is in flight: its span resumes at the fetch.
        t_dispatch = step.pause()
        return {
            'tokens': tokens,
            'plan': plan,
            'last_ids': last_ids,
            't_dispatch': t_dispatch,
            'chunk_tokens': chunk_tokens,
            'chunk_plan': chunk_entries,
            'context_lens': context_arrays,
            # A family's extra, fetched with the window's tokens: its
            # (routed, held) expert pairs, or a dict of named counters
            # summed on the device over the window's steps.
            'moe_pairs': moe_pairs,
            # With a windowed cache group: what its rows held of it.
            'window_fields': window_fields,
            # Blocks of positions: rid -> the given tokens its rows of
            # ``tokens`` start with (not emitted).
            'given': given_of,
            # The step's span so far (admit/plan/put/dispatch), completed
            # with fetch and emit when _process_window syncs the tokens.
            'step': step,
        }

    # ------------------------------------------- speculative verify windows
    def _dispatch_spec_window(self, step: _steps.StepSpan) -> dict | object:
        """Plan and dispatch one speculative verify window
        (docs/speculative.md).

        For every decode-ready row the prompt-lookup drafter proposes up
        to ``draft_k`` tokens from the row's own history; the row's span
        ``[last_emitted_token, drafts...]`` rides ONE ragged dispatch
        (``mistral.spec_window`` — the same write-then-attend kernel as
        paged prefill) that scores all positions in a single weight pass.
        Block headroom is reserved PER ROW (``prepare_decode(..., ks)``):
        each row gets exactly its own span, not the batch max. Composes
        with mixed batching — pending prefill-chunk rows ride the same
        dispatch through the chunk-carrying variant. Returns the window
        record for ``_process_spec_window``, or ``_DRAIN`` when nothing
        can ride.
        """
        self._faults.fail('dispatch')  # same site as the classic window
        step.mark('plan')
        cfg = self.config
        draft_k = cfg.draft_k
        drafts_by_rid: dict[int, list[int]] = {}
        decode_rids: list[int] = []
        row_ks: list[int] = []
        for _, rid in self.sched.running():
            request = self._requests[rid]
            if not self._decode_ready(request):
                continue
            # The drafter may propose at most budget-1 tokens: a window
            # emits accepted+1 tokens, and emission must never overshoot
            # max_tokens / max_model_len (spec discards nothing emitted).
            budget = self._window_budget(request, 0, draft_k + 1)
            if budget <= 0:
                continue
            drafts: list[int] = []
            if budget > 1 and request.drafter is not None:
                drafts = request.drafter.draft(
                    request.prompt_ids + request.output_ids,
                    min(draft_k, budget - 1),
                )
            drafts_by_rid[rid] = drafts
            decode_rids.append(rid)
            # Per-row headroom: the span writes K/V up to position
            # num_tokens - 1 + len(drafts), i.e. num_tokens + len(drafts)
            # tokens of coverage; 1 keeps the classic single-step floor.
            row_ks.append(max(1, len(drafts)))
        if decode_rids:
            short = self._reserve_shortfall(
                dict(zip(decode_rids, row_ks))
            ) - self.sched.num_free_blocks
            short -= self._evict_cached_blocks(short)
            # Spec windows process synchronously, so victims never have
            # in-flight tokens; recompute preemption re-prefills them
            # (preemption mid-draft: the un-dispatched draft is simply
            # dropped with the rest of the row's state).
            for rid in self._prepare_decode(
                step, short, 1, decode_rids, row_ks
            ):
                drafts_by_rid.pop(rid, None)
        chunk_plan = self._plan_window_chunks()

        b = cfg.max_num_seqs
        span = 1 + draft_k
        spans: list = [(None, 0, 0)] * b
        token_rows: list = [[]] * b
        temperature = np.zeros((b,), np.float32)
        top_p = np.ones((b,), np.float32)
        min_p = np.zeros((b,), np.float32)
        top_k = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.uint32)
        plan: list[tuple[int, int, list[int]]] = []
        for slot, rid in self.sched.running():
            drafts = drafts_by_rid.get(rid)
            if drafts is None:
                continue
            request = self._requests[rid]
            if request.state is not RequestState.RUNNING:
                continue
            last = (
                request.output_ids[-1]
                if request.output_ids
                else request.prompt_ids[-1]
            )
            # The span starts at the last emitted token's position (its
            # K/V is not yet written — decode's write-then-attend
            # contract) and extends through the drafts.
            spans[slot] = (request, request.num_tokens - 1, 1 + len(drafts))
            token_rows[slot] = [last] + drafts
            temperature[slot] = request.params.temperature
            top_p[slot] = request.params.top_p
            min_p[slot] = request.params.min_p
            top_k[slot] = request.params.top_k
            seeds[slot] = request.sample_seed
            plan.append((slot, rid, drafts))
        if not plan and not chunk_plan:
            return _DRAIN

        ids, positions, block_rows, context_lens, tail_lens = (
            self._span_host_arrays(spans, span, b, token_rows=token_rows)
        )
        host_arrays = [
            ids, positions, block_rows, context_lens, tail_lens,
            temperature, top_p, min_p, top_k, seeds,
        ]
        context_arrays = [context_lens]
        if chunk_plan:
            chunk_arrays = self._build_chunk_arrays(chunk_plan)
            context_arrays.append(chunk_arrays[3])
            host_arrays.extend(chunk_arrays)
        step.mark('put')
        devs = self._put_many(*host_arrays)
        step.mark('spec')
        chunk_tokens = None
        chunk_entries: list[tuple[int, int, int, int, bool]] = []
        if chunk_plan:
            tokens, self.kv.k_pool, self.kv.v_pool, chunk_tokens = self._call(
                self._spec_mixed_window,
                self.params,
                devs[0],  # span ids
                devs[1],  # span positions
                devs[3],  # context_lens
                self.kv.k_pool,
                self.kv.v_pool,
                devs[2],  # block tables
                devs[4],  # span_lens
                devs[5],
                devs[6],
                devs[7],
                devs[8],  # top_k
                devs[9],  # seeds
                *devs[10:],
            )
            ridden = 0
            for i, (request, start, ntok) in enumerate(chunk_plan):
                request.prefill_sent = start + ntok
                final = start + ntok >= request.prefill_target
                chunk_entries.append(
                    (i, request.request_id, start, ntok, final)
                )
                ridden += ntok
            # The ridden-prefill series stay truthful regardless of which
            # window kind carried the chunks; the WINDOW itself counts as
            # spec (one dispatch is one window).
            self._stats['spec_chunk_windows'] += 1
            self._stats['mixed_prefill_tokens'] += ridden
            _metrics.MIXED_PREFILL_TOKENS.inc(ridden)
            _metrics.MIXED_PREFILL_TOKENS_PER_WINDOW.observe(ridden)
            _metrics.MIXED_PREFILL_ROWS.observe(len(chunk_plan))
        else:
            tokens, self.kv.k_pool, self.kv.v_pool, _ = self._call(
                self._spec_window,
                self.params,
                devs[0],
                devs[1],
                devs[3],
                self.kv.k_pool,
                self.kv.v_pool,
                devs[2],
                devs[4],
                devs[5],
                devs[6],
                devs[7],
                devs[8],
                devs[9],
            )
        ndrafted = sum(len(d) for _, _, d in plan)
        self._stats['spec_windows'] += 1
        self._stats['spec_draft_tokens'] += ndrafted
        _metrics.SPEC_WINDOWS.inc()
        if ndrafted:
            _metrics.SPEC_DRAFT_TOKENS.inc(ndrafted)
        t_dispatch = step.pause()
        return {
            'spec': True,
            'tokens': tokens,
            'plan': plan,
            'chunk_tokens': chunk_tokens,
            'chunk_plan': chunk_entries,
            't_dispatch': t_dispatch,
            'last_ids': None,
            'context_lens': context_arrays,
            'step': step,
        }

    def _process_spec_window(self, window: dict) -> list[tuple[int, int]]:
        """Fetch one verify window's tokens and run the greedy acceptance
        decisions already made device-side (the only host sync of the
        speculative path).

        The packed fetch is ``[B, S+1]``: per-position output tokens plus
        the accepted-draft count computed by ``verify_spans`` inside the
        dispatch — greedy argmax comparison for temperature-0 rows,
        exact rejection sampling for sampled rows (docs/speculative.md
        "Sampled verification"). Token 0 is always emitted (it follows
        the last REAL token); tokens 1..accept_len are the accepted
        drafts' successors, and token accept_len is the correction /
        bonus, so the output stream is exactly the sequential stream
        (each accepted draft skipped one weight pass). EOS / max_tokens
        inside the accepted prefix finish the request mid-span and the
        remaining verified tokens are discarded. Rejected suffixes roll
        back: ``sched.trim`` returns the unused per-row headroom so
        scheduler state matches a never-drafted run (the rejected K/V
        needs no rollback — it sits at positions every later dispatch
        overwrites before attending or masks out).
        """
        step = window['step']
        step.mark('fetch')
        # distlint: disable=host-sync-in-hot-path -- the spec window's ONE designed fetch point: emission needs the verified tokens + accept length on host, and spec windows process synchronously (depth 1)
        tokens = np.asarray(window['tokens'])  # [B, S+1] packed
        self._fetching = None
        window['duration_s'] = step.mark('emit') - window['t_dispatch']
        gauges = self._sched_gauges()
        emitted: list[tuple[int, int]] = []
        drafted = accepted = rows = 0
        sampled_rows = resampled = 0
        for slot, rid, drafts in window['plan']:
            request = self._requests.get(rid)
            if request is None or request.state is not RequestState.RUNNING:
                continue  # finished/preempted during an abnormal drain
            rows += 1
            drafted += len(drafts)
            sampled = request.params.temperature > 0
            if sampled and drafts:
                sampled_rows += 1
            n_acc = min(int(tokens[slot, -1]), len(drafts))
            if sampled and drafts and n_acc < len(drafts):
                # A sampled row that stopped short burned one residual
                # resample (the correction token).
                resampled += 1
            token = int(tokens[slot, 0])
            self._emit_token(request, token)
            emitted.append((rid, token))
            for i in range(n_acc):
                if rid not in self._requests:
                    break  # finished (EOS / max_tokens): discard the rest
                accepted += 1
                token = int(tokens[slot, i + 1])
                self._emit_token(request, token)
                emitted.append((rid, token))
            if rid in self._requests and request.state is RequestState.RUNNING:
                self.sched.trim(rid)
        self._stats['spec_accepted_tokens'] += accepted
        self._stats['spec_sampled_rows'] += sampled_rows
        self._stats['spec_resampled_tokens'] += resampled
        if accepted:
            _metrics.SPEC_ACCEPTED_TOKENS.inc(accepted)
        if drafted:
            _metrics.SPEC_ACCEPT_RATE.observe(accepted / drafted)
        if sampled_rows:
            _metrics.SPEC_SAMPLED_ROWS.inc(sampled_rows)
        if resampled:
            _metrics.SPEC_RESAMPLED_TOKENS.inc(resampled)
        chunk_entries = window.get('chunk_plan') or []
        extra = {
            'draft_tokens': drafted,
            'accepted_tokens': accepted,
            'sampled_rows': sampled_rows,
            'resampled_tokens': resampled,
        }
        if chunk_entries:
            extra['prefill_tokens'] = sum(
                n for *_, n, _ in chunk_entries
            )
            extra['prefill_rows'] = len(chunk_entries)
        ntokens = len(emitted)
        emitted.extend(self._process_chunk_entries(window))
        step.close()
        self._record_step(
            'spec', step, batch=rows, tokens=ntokens,
            duration_s=window['duration_s'], gauges=gauges,
            kv_blocks=self._kv_blocks(*window['context_lens']), **extra,
        )
        return emitted

    def _prepare_decode(self, step: _steps.StepSpan, short: int, k: int,
                        rids, ks=None) -> list[int]:
        """``sched.prepare_decode`` and the sync of its victims; under a
        ``distllm:preempt`` span when blocks are still ``short`` after
        eviction, which is when it preempts. Preemptions performed before
        a fatal exhaustion are not rolled back: their states are synced
        too, so a caller that catches and continues sees engine state
        consistent with the scheduler."""
        span = step.inside('preempt') if short > 0 else contextlib.nullcontext()
        with span:
            try:
                preempted = self.sched.prepare_decode(k, rids, ks)
            except SchedulerExhausted as exc:
                self._budget_use_was_low()
                self._note_preempted(exc.preempted, k, step)
                raise
            if preempted:
                self._budget_use_was_low()
            self._note_preempted(preempted, k, step)
        return preempted

    def _note_preempted(self, rids: list[int], k: int,
                        step: _steps.StepSpan) -> None:
        """Sync the victims of one ``prepare_decode`` and write its
        ``preempt`` record: per rid the tokens whose KV was freed and must
        be prefilled again."""
        if not rids:
            return
        lost = [self._on_preempt(self._requests[rid]) for rid in rids]
        self.flight.record(
            'preempt',
            rids=list(rids),
            tokens_lost=lost,
            k=k,
            free_blocks=self.sched.num_free_blocks,
            running=self.sched.num_running,
            queue_depth=self.sched.num_waiting,
            seq=step.seq,
        )

    def _on_preempt(self, request: Request) -> int:
        """Fold one recompute preemption into the request; returns the
        tokens it lost (``num_tokens`` less the cached prefix it keeps)."""
        request.state = RequestState.WAITING
        request.preemptions += 1
        self._release_window_blocks(request.request_id)
        # A promotion in flight for the victim is simply dropped: its
        # scatter is already dispatched (ordering protects later readers)
        # and the blocks it adopted are borrowed — preemption keeps them,
        # so re-admission resumes from the promoted coverage for free.
        self._promoting.pop(request.request_id, None)
        if self.prefix_cache is not None:
            # Recompute preemption kept only the borrowed (cache-owned)
            # prefix; everything past it was freed and must re-prefill.
            request.num_cached_tokens = (
                request.num_borrowed_blocks * self.config.block_size
            )
        # Mixed chunk progress is recompute state too: chunks past the
        # borrowed prefix lived in the freed owned blocks. target 0 =
        # decode-ready-by-default; re-admission re-enrolls (or prefills
        # standalone) with a fresh target.
        request.prefill_target = 0
        request.prefill_sent = request.num_cached_tokens
        request.prefill_done = request.num_cached_tokens
        try:
            self._prefilling.remove(request.request_id)
        # distlint: disable=swallowed-exception -- membership-probe control flow: the victim simply was not mid-prefill, nothing degraded
        except ValueError:
            pass
        return request.num_tokens - request.num_cached_tokens

    def _process_window(self, window: dict) -> list[tuple[int, int]]:
        """Fetch one window's tokens (the only host sync in the decode
        path) and fold them into request state; post-EOS overshoot tokens
        are discarded (counted in ``_stats['overshoot_tokens']`` — the
        bounded waste the pipelined EOS-one-window-late design trades for
        hidden dispatch latency). Speculative windows carry a different
        token layout and acceptance rule and route to
        ``_process_spec_window``."""
        self._fetching = window  # in flight until its tokens are here
        if window.get('spec'):
            return self._process_spec_window(window)
        # Injection site 'slow_window': the stall hazard — the sleep sits
        # where a wedged device fetch would, so watchdogs and per-request
        # deadlines see exactly what they would see in production.
        self._faults.maybe_sleep('slow_window')
        # A deferred prefill's fetch record carries no step of its own
        # (its prefill record is written): its two spans ride a bare one.
        recorded = 'step' in window
        step = window['step'] if recorded else self._begin_step()
        step.mark('fetch')
        # distlint: disable=host-sync-in-hot-path -- the window loop's ONE designed fetch point: processing happens a window late, after the next dispatch is already in flight (pipeline_depth hides this sync)
        tokens = np.asarray(window['tokens'])  # [K, B]
        moe_pairs = window.get('moe_pairs')
        counters = None  # a family's named counters in the pairs' place
        decided_at = None  # per token, where blocks are decided together
        if isinstance(moe_pairs, dict):
            # distlint: disable=host-sync-in-hot-path -- a few int32 the window's program wrote with the tokens fetched one line up: ready, never waited for
            counters = {name: np.asarray(n).tolist() for name, n in moe_pairs.items() if name != 'decided_at'}
            if 'decided_at' in moe_pairs:
                # distlint: disable=host-sync-in-hot-path -- written with the tokens fetched above: ready, never waited for
                decided_at = np.asarray(moe_pairs['decided_at'])
            moe_pairs = None
        if moe_pairs is not None:
            # distlint: disable=host-sync-in-hot-path -- two int32 the window's program wrote with the tokens fetched one line up: ready, never waited for
            moe_pairs = np.asarray(moe_pairs)
        t_fetched = step.mark('emit')
        self._fetching = None
        emitted: list[tuple[int, int]] = []
        chunk_entries = window.get('chunk_plan') or []
        if recorded:
            window['duration_s'] = t_fetched - window['t_dispatch']
            gauges = self._sched_gauges()
        for slot, rid, steps in window['plan']:
            if rid in self._unacked:
                self._unacked[rid] = max(0, self._unacked[rid] - steps)
            if rid not in self._requests:
                self._stats['overshoot_tokens'] += steps
                _metrics.ENGINE_OVERSHOOT_TOKENS.inc(steps)
                continue  # finished in an earlier window; overshoot tokens
            request = self._requests[rid]
            if request.state is not RequestState.RUNNING:
                continue  # preempted while idle; will re-prefill
            skip = window.get('given', {}).get(rid, 0)
            for i in range(skip, skip + steps):
                token = int(tokens[i, slot])
                if decided_at is not None:
                    request.decided_at.append(int(decided_at[i, slot]))
                self._emit_token(request, token)
                emitted.append((rid, token))
                if rid not in self._requests:
                    self._stats['overshoot_tokens'] += skip + steps - i - 1
                    _metrics.ENGINE_OVERSHOOT_TOKENS.inc(skip + steps - i - 1)
                    break  # finished mid-window
        emitted.extend(self._process_chunk_entries(window))
        step.close()
        if recorded:
            extra = {}
            if chunk_entries:
                extra = {
                    'prefill_tokens': sum(n for *_, n, _ in chunk_entries),
                    'prefill_rows': len(chunk_entries),
                }
            if counters is not None:
                extra = dict(counters)
                if 'forwards' in counters:
                    _metrics.DENOISE_FORWARDS.inc(counters['forwards'])
                    _metrics.BLOCK_POSITIONS_DECIDED.inc(counters['decided'])
            if moe_pairs is not None:
                extra = {
                    'moe_pairs': int(moe_pairs[0]),
                    'moe_pairs_held': int(moe_pairs[1]),
                }
            if not chunk_entries:  # a decode window runs every slot's row
                extra.update(self._moe_form_field(tokens.shape[1] * self._block))
            extra.update(self._loop_fields)
            kv_blocks = self._kv_blocks(*window['context_lens'])
            if window.get('window_fields'):
                extra.update(window['window_fields'], kv_blocks_full=kv_blocks)
            if not chunk_entries:  # a mixed window's rows ride a span program
                extra.update(self._kv_chunks(window['context_lens'][0]))
                extra.update(self._walk_block_fields)
            self._record_step(
                'mixed' if chunk_entries else 'decode',
                step,
                batch=sum(1 for _, _, s in window['plan'] if s > 0),
                tokens=sum(s for _, _, s in window['plan']),
                duration_s=window['duration_s'], gauges=gauges,
                kv_blocks=kv_blocks, **extra,
            )
        return emitted

    def _process_chunk_entries(self, window: dict) -> list[tuple[int, int]]:
        """Fold a fetched window's ridden prefill-chunk spans into request
        state (shared by the mixed decode and speculative processors).
        The caller's token fetch is the completion barrier: once the
        window's tokens are on host, its chunk K/V writes are in the
        cache."""
        chunk_entries = window.get('chunk_plan') or []
        emitted: list[tuple[int, int]] = []
        if not chunk_entries:
            return emitted
        # distlint: disable=host-sync-in-hot-path -- the mixed window's designed chunk-token fetch: runs after the caller's token fetch already synced this window, so no extra device round-trip is added
        chunk_tokens = np.asarray(window['chunk_tokens'])
        for row_i, rid, start, ntok, final in chunk_entries:
            request = self._requests.get(rid)
            if request is None or request.state is not RequestState.RUNNING:
                continue  # preempted during an abnormal drain
            request.prefill_done = max(
                request.prefill_done, start + ntok
            )
            # A chunk that rode this window is a prefill dispatch of its
            # request, on the 'mixed' route, for the window's seconds.
            self._note_prefill([(request, ntok)], 'mixed')
            self._note_prefill_seconds(
                [request], window['duration_s'], window['t_dispatch']
            )
            if final:
                # Freshly prefilled full prompt blocks enter the
                # prefix cache BEFORE emission — a max_tokens=1
                # request finishes inside _emit_token, after which
                # its row is gone (same ordering as the standalone
                # paths).
                self._insert_prompt_blocks(request)
                try:
                    self._prefilling.remove(rid)
                # distlint: disable=swallowed-exception -- membership-probe control flow: a re-enrolled span may already be off the list, nothing degraded
                except ValueError:
                    pass
                token = int(chunk_tokens[row_i])
                self._emit_token(request, token)
                emitted.append((rid, token))
        return emitted

    def _run_to_completion(self) -> None:
        """Drive every request to a terminal state.

        With ``max_dispatch_retries == 0`` (default) this is exactly the
        legacy contract: the first dispatch exception propagates. With
        recovery on, a failed serving pass — its in-flight windows
        already folded back by ``_serve_pipelined``'s cleanup — charges
        the involved requests, quarantines the ones past the retry
        budget, backs off, and re-enters the loop: the engine either
        recovers or fails *only* the affected requests, never wedges
        (docs/resilience.md "Crash-domain recovery")."""
        while True:
            try:
                self._serve_pipelined()
                return
            except Exception as exc:
                if not self._recover(exc):
                    raise

    def _serve_pipelined(self) -> None:
        """Drive all requests to completion with ``pipeline_depth`` decode
        windows in flight, so the host's token fetch and scheduling hide
        behind the next window's compute. EOS and admission react one
        window late — bounded overshoot, unchanged results.

        Speculative mode (``draft_k > 0``) forces depth 1: the prompt-
        lookup drafter needs each window's host-fetched tokens before it
        can propose the next span, so windows process synchronously and
        the latency trade shifts from dispatch-hiding to weight-pass-
        skipping (docs/speculative.md)."""
        from collections import deque

        depth = (
            1 if self.config.draft_k else max(1, self.config.pipeline_depth)
        )
        inflight: deque[dict] = deque()
        self._inflight = inflight
        self._carried = None

        def process_one() -> None:
            self._process_window(inflight.popleft())

        def drain_one() -> None:
            if inflight:
                process_one()

        self._drain_hook = drain_one
        self._windows_behind = depth - 1
        root = self._begin_root()
        try:
            while self.has_unfinished or inflight:
                if self._expired_requests():
                    # Deadline expiry frees the victims' blocks, which is
                    # only safe with nothing in flight (an in-flight
                    # window still writes to them) — drain first. A
                    # deadline event is rare; the drain is cheap next to
                    # the seconds the request already burned.
                    while inflight:
                        process_one()
                    self._expire_deadlines()
                # Deferred prefill (opt-in): first tokens stay on device
                # (scattered into self._carried) and their fetch records
                # join the in-flight deque instead of blocking the decode
                # pipeline. See EngineConfig.defer_prefill for why the
                # default is the synchronous path.
                span = self._begin_step()
                span.mark('admit')
                self._admit(
                    defer_to=inflight if self.config.defer_prefill else None
                )
                if self.sched.num_running == 0:
                    span.close()
                    if inflight:
                        process_one()
                    continue
                if inflight and self._head_waits_on_inflight():
                    span.close()
                    process_one()
                    continue
                # Never let a dispatch preempt while windows are in flight.
                # Evictable cached blocks count as free capacity first.
                while inflight and (
                    short := self._reserve_shortfall(self._window_reserve())
                    - self.sched.num_free_blocks
                ) > 0:
                    if self._evict_cached_blocks(short):
                        continue
                    process_one()
                window = self._dispatch_window(self._carried, span)
                if window is _DRAIN:
                    span.close()
                    if inflight:
                        process_one()
                    continue
                self._carried = window['last_ids']
                inflight.append(window)
                if len(inflight) >= depth:
                    process_one()
        except BaseException:
            _steps.abandon()
            # Keep catch-and-continue recovery sound (the SchedulerExhausted
            # contract): fold every dispatched window back into request
            # state so no _unacked counts, device-side tokens, or in-flight
            # chunk spans are orphaned.
            while inflight:
                try:
                    process_one()
                except Exception as drain_exc:
                    _steps.abandon()
                    # Abnormal drain: the in-flight windows cannot be
                    # folded back — their device-side tokens are lost
                    # (KV writes at positions >= num_tokens are
                    # overwritten before they are ever attended).
                    # Recorded, never silent: a recovery retry that
                    # starts from a drained pipeline should say so.
                    self.flight.record(
                        'event',
                        event='abnormal_drain',
                        dropped_windows=len(inflight) + 1,
                        error=repr(drain_exc)[:200],
                    )
                    inflight.clear()
                    self._unacked.clear()
            # The mixed analogue of clearing _unacked: a chunk span whose
            # window was dropped above advanced prefill_sent but never
            # prefill_done — rolling sent back lets the span re-ride after
            # a catch-and-continue resume (otherwise the planner skips the
            # request as 'in flight' forever and the loop livelocks).
            for rid in self._prefilling:
                request = self._requests.get(rid)
                if request is not None:
                    request.prefill_sent = request.prefill_done
            raise
        finally:
            root.close()
            self._inflight, self._fetching = (), None
            self._drain_hook = None
            self._windows_behind = 0

    # ------------------------------------- crash-domain recovery (faults)
    def _recover(self, exc: Exception) -> bool:
        """Decide whether a failed serving pass retries
        (docs/resilience.md "Crash-domain recovery").

        True = retry: the failure is charged to every involved request
        (the running batch — or the waiting head when admission itself
        failed with nothing running), requests past the
        ``max_dispatch_retries`` budget are quarantined to FAILED with
        the error recorded, and a bounded exponential backoff sleeps off
        transient faults. False = recovery disabled or unattributable —
        the caller re-raises. Termination is structural: every True
        return charges at least one live request and each request is
        quarantined after at most ``max_dispatch_retries + 1`` charges,
        so a permanent fault drains the request population into FAILED
        instead of livelocking the loop.

        Callers guarantee no windows are in flight (the pipelined loop's
        exception cleanup already folded them back), so quarantine may
        free blocks safely.
        """
        cfg = self.config
        if cfg.max_dispatch_retries <= 0:
            return False
        involved = [rid for _, rid in self.sched.running()]
        if not involved:
            waiting = [
                r.request_id
                for r in self._requests.values()
                if r.state is RequestState.WAITING
            ]
            if waiting:
                involved = [min(waiting)]
        if not involved:
            return False  # nothing live to charge: unattributable
        self._consecutive_failures += 1
        self._stats['window_retries'] += 1
        _metrics.RESILIENCE_RETRIES.inc()
        for rid in involved:
            self._dispatch_failures[rid] = (
                self._dispatch_failures.get(rid, 0) + 1
            )
        self.flight.record(
            'recovery',
            status='retry',
            error=repr(exc)[:200],
            attempt=self._consecutive_failures,
            rids=involved[:16],
        )
        for rid in involved:
            if (
                self._dispatch_failures.get(rid, 0)
                > cfg.max_dispatch_retries
            ):
                request = self._requests.get(rid)
                if request is not None:
                    self._fail_request(
                        request,
                        reason='dispatch_failed',
                        error=repr(exc)[:300],
                    )
        delay = cfg.retry_backoff_s * (
            2 ** min(self._consecutive_failures - 1, 6)
        )
        if delay > 0:
            time.sleep(min(delay, 2.0))
        return True

    def _expired_requests(self) -> list[Request]:
        """Live requests past ``request_deadline_s`` (empty when the
        deadline is off) — the cheap guard the serving loops poll."""
        deadline = self.config.request_deadline_s
        if deadline <= 0 or not self._requests:
            return []
        now = time.monotonic()
        return [
            r
            for r in self._requests.values()
            if r.state
            in (RequestState.WAITING, RequestState.RUNNING)
            and now - r.t_enqueue > deadline
        ]

    def _expire_deadlines(self) -> None:
        """Quarantine every request past its wall-clock deadline with
        ``finish_reason='timeout'``, freeing its KV blocks instead of
        holding them forever. Callers must have no windows in flight."""
        for request in self._expired_requests():
            self._fail_request(
                request,
                reason='timeout',
                error=(
                    'request exceeded request_deadline_s='
                    f'{self.config.request_deadline_s}'
                ),
            )

    def _fail_request(
        self, request: Request, *, reason: str, error: str
    ) -> None:
        """Terminal quarantine: record the error, free every resource the
        request holds, and park it in the finished map as FAILED — never
        a silent drop (one ``'quarantine'`` flight record + the
        ``distllm_resilience_quarantined_requests_total{reason}``
        counter). Callers must have no windows in flight: quarantine
        frees blocks, and an in-flight window could still write to them.
        """
        rid = request.request_id
        request.state = RequestState.FAILED
        request.finish_reason = reason
        request.error = error
        request.t_finish = time.monotonic()
        _metrics.RESILIENCE_QUARANTINED.labels(reason=reason).inc()
        self._stats['quarantined_requests'] += 1
        self.flight.record(
            'quarantine',
            request_id=rid,
            trace_id=request.trace_id,
            reason=reason,
            error=error[:300],
            prompt_tokens=len(request.prompt_ids),
            output_tokens=len(request.output_ids),
        )
        self.sched.finish(rid)
        self._release_window_blocks(rid)
        if self.prefix_cache is not None:
            self.prefix_cache.release(rid)
        self._promoting.pop(rid, None)
        self._unacked.pop(rid, None)
        self._dispatch_failures.pop(rid, None)
        for pending in (self._prefilling, self._pending_prefill):
            try:
                pending.remove(rid)
            # distlint: disable=swallowed-exception -- membership-probe control flow: the rid simply was not mid-prefill, nothing degraded
            except ValueError:
                pass
        del self._requests[rid]
        self._finished[rid] = request

    def _sample_device(
        self, logits: jnp.ndarray, slots,
        step: _steps.StepSpan | None = None,
    ) -> jnp.ndarray:
        """Sample one token per row on DEVICE (no host sync). ``step``
        (the prefill step the rows belong to) takes ``sampled_rows``."""
        b = logits.shape[0]
        temperature = np.zeros((b,), np.float32)
        top_p = np.ones((b,), np.float32)
        min_p = np.zeros((b,), np.float32)
        top_k = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.uint32)
        counters = np.zeros((b,), np.int32)
        for i, request in enumerate(slots):
            if request is None:
                continue
            temperature[i] = request.params.temperature
            top_p[i] = request.params.top_p
            min_p[i] = request.params.min_p
            top_k[i] = request.params.top_k
            seeds[i] = request.sample_seed
            # The prompt occupies absolute indices 0..num_tokens-1, so
            # the first generated token's index — its PRNG counter — is
            # num_tokens (matches the decode scan's pos + 1 convention).
            counters[i] = request.num_tokens
        if step is not None:
            step.counts['sampled_rows'] = _sampled_rows(temperature)
        t_dev, tp_dev, mp_dev, tk_dev, sd_dev, ct_dev = self._put_many(
            temperature, top_p, min_p, top_k, seeds, counters
        )
        return self._call(
            self._sample, logits, t_dev, tp_dev, mp_dev, tk_dev, sd_dev, ct_dev
        )

    def _emit_token(self, request: Request, token: int) -> None:
        # Note: the emitted token is NOT yet written to the KV cache; it is
        # fed as input on the next decode step, which writes it then.
        if self._consecutive_failures:
            # First token after one or more failed dispatches: the retry
            # ladder worked — record the recovery, reset the backoff.
            self._consecutive_failures = 0
            self._stats['recoveries'] += 1
            _metrics.RESILIENCE_RECOVERIES.inc()
            self.flight.record(
                'recovery', status='recovered',
                request_id=request.request_id,
            )
        if self._dispatch_failures:
            # Progress clears a request's failure charge: only
            # CONSECUTIVE failures quarantine (poison containment), not
            # failures spread across an otherwise healthy run.
            self._dispatch_failures.pop(request.request_id, None)
        if not request.output_ids and request.t_first_token == 0.0:
            # TTFT is measured to the HOST fetch of the first token — the
            # latency a streaming client sees, including any pipelined lag.
            request.t_first_token = time.monotonic()
            _metrics.REQUEST_TTFT.observe(
                request.t_first_token - request.t_enqueue
            )
        request.output_ids.append(token)
        self.sched.append_token(request.request_id)
        _metrics.ENGINE_GENERATED_TOKENS.inc()
        eos = getattr(self.tokenizer, 'eos_id', None)
        stops = set(request.params.stop_token_ids)
        if eos is not None:
            stops.add(eos)
        if (
            token in stops
            or len(request.output_ids) >= request.params.max_tokens
            or request.num_tokens >= self.config.max_model_len
        ):
            request.finish_reason = 'stop' if token in stops else 'length'
            self._finish(request)

    def _finish(self, request: Request) -> None:
        request.state = RequestState.FINISHED
        request.t_finish = time.monotonic()
        # What it used of its budget, for the decode-budget walk's
        # expected ends (1.0 until something has finished; 1.0 again
        # wherever requests run to max_tokens).
        used = len(request.output_ids)
        self._ewma_update(
            'budget_use', used / (used + self._budget_left(request))
        )
        self._observe_lifecycle(request)
        _metrics.ENGINE_REQUESTS_FINISHED.inc()
        self.sched.finish(request.request_id)
        self._release_window_blocks(request.request_id)
        if self.prefix_cache is not None:
            # Drop this request's references; ref==0 blocks become LRU-
            # evictable but KEEP their KV — that persistence is what makes
            # the next same-prefix request free.
            self.prefix_cache.release(request.request_id)
        self._unacked.pop(request.request_id, None)
        del self._requests[request.request_id]
        self._finished[request.request_id] = request

    def _observe_lifecycle(self, request: Request) -> None:
        """Fold one finished request into the lifecycle series and the
        flight ring: TTFT / TPOT histograms, SLO + goodput counters when an
        SLO is configured, and one ``'request'`` flight record carrying the
        whole enqueue→admit→first-token→finish timeline."""
        n_out = len(request.output_ids)
        ttft_s = (
            request.t_first_token - request.t_enqueue
            if request.t_first_token else None
        )
        tpot_s = None
        if request.t_first_token and n_out > 1:
            tpot_s = (request.t_finish - request.t_first_token) / (n_out - 1)
            _metrics.REQUEST_TPOT.observe(tpot_s)
        slo = self.config.ttft_slo_s
        if slo > 0 and ttft_s is not None:
            met = ttft_s <= slo
            _metrics.REQUEST_SLO.labels(
                outcome='met' if met else 'missed'
            ).inc()
            self._stats['slo_met' if met else 'slo_missed'] += 1
            if met:
                _metrics.GOODPUT_TOKENS.inc(n_out)
                self._stats['goodput_tokens'] += n_out
        self.flight.record(
            'request',
            request_id=request.request_id,
            trace_id=request.trace_id,
            prompt_tokens=len(request.prompt_ids),
            output_tokens=n_out,
            queue_wait_s=round(request.t_admit - request.t_enqueue, 6)
            if request.t_admit else None,
            ttft_s=round(ttft_s, 6) if ttft_s is not None else None,
            tpot_s=round(tpot_s, 6) if tpot_s is not None else None,
            # Full enqueue -> finish extent: what lets the Perfetto
            # exporter reconstruct the request's wall-clock slice from
            # this one record (t_wall is the finish instant).
            e2e_s=round(request.t_finish - request.t_enqueue, 6),
            cached_tokens=request.num_cached_tokens,
            # Counted where it happened (docs/observability.md
            # "Serving-path spans"): preemptions, the prefill dispatches
            # it rode by route, all tokens prefilled for it (re-prefill
            # included), the seconds of the prefill steps before its
            # first token, and admission and first token on the step
            # records' clock.
            preemptions=request.preemptions,
            prefill_tokens=request.prefill_tokens,
            routes=dict(request.routes),
            prefill_first_s=round(request.prefill_first_s, 6),
            t_admit_s=round(request.t_admit, 6) if request.t_admit else None,
            t_first_s=round(request.t_first_token, 6)
            if request.t_first_token else None,
            **self._state_slot_field(request),
            **self._kv_ends_field(request),
            **self._blocks_field(request),
        )

    def _blocks_field(self, request: Request) -> dict:
        """Where blocks of positions are decided together: the blocks the
        request's output took (its time to first token is its prefill plus
        the first window of them) and, per output token, the denoise step
        it was decided at."""
        if self._block == 1:
            return {}
        given = len(request.prompt_ids) % self._block
        return {
            'blocks': -(-(given + len(request.output_ids)) // self._block),
            'decided_at': list(request.decided_at),
        }

    def _state_slot_field(self, request: Request) -> dict:
        """The slot of a hybrid's state pool a request held when it
        finished (it is freed right after this record; the pool keeps what
        the slot held until its next holder's first prefill span)."""
        if self.state_pool is None:
            return {}
        return {'state_slot': self.sched.slot(request.request_id)}

    def _kv_ends_field(self, request: Request) -> dict:
        """For a model with a windowed or a latent cache group, with a
        state pool, or whose stack runs several times: the ids of
        two blocks of the full-context group the request held when it
        finished, its
        first and the one that holds the last position it wrote (its last
        token was never fed). Both are freed right after this record; the
        pool keeps what a freed block held until its next holder writes it,
        which is how the benchmark's check reads the K/V a finished request
        left. With a windowed group, ``kv_window_first_index``,
        ``kv_window_first_block`` and ``kv_window_tail_block`` beside them:
        that group's ends (``WindowBlocks.ends``)."""
        if (
            self.window_kv is None and not self.cache_spec.latent
            and self.state_pool is None and self.cache_spec.passes == 1
            and self._block == 1
        ):
            return {}
        row = self.sched.block_row(request.request_id)
        written = len(request.prompt_ids) + len(request.output_ids) - 1
        if self._block > 1:
            # Every token it emitted was committed with its block: the tail
            # is the page of the last WHOLE block of the tokens it has (a
            # last block cut by the budget has positions nobody was given).
            written = (written + 1) // self._block * self._block
        if not row or written < 1:
            return {}
        tail = min((written - 1) // self.config.block_size, len(row) - 1)
        ends = {'kv_first_block': row[0], 'kv_tail_block': row[tail]}
        if self.window_blocks is not None:
            ends.update(self.window_blocks.ends(request.request_id, tail))
        return ends

    # -------------------------------------------------------------- offline
    def generate_ids(
        self,
        prompts: list[list[int]],
        params: SamplingParams | None = None,
    ) -> list[list[int]]:
        """Offline batch API: token ids in, generated token ids out."""
        import time as _time

        self._stats.clear()
        ids = [self.add_request(p, params) for p in prompts]
        loop_start = _time.perf_counter()
        self._run_to_completion()
        loop_s = _time.perf_counter() - loop_start
        n_out = sum(len(r.output_ids) for r in self._finished.values())
        self.telemetry.update(
            {k: int(v) for k, v in self._stats.items()}
        )
        self.telemetry['decode_loop_s'] = round(loop_s, 3)
        windows = self._stats.get('decode_windows', 0)
        if windows and loop_s > 0:
            self.telemetry['windows_per_s'] = round(windows / loop_s, 2)
        lookups = self._stats.get('prefix_lookup_tokens', 0)
        if lookups:
            self.telemetry['prefix_hit_rate'] = round(
                self._stats.get('prefix_hit_tokens', 0) / lookups, 4
            )
        drafted = self._stats.get('spec_draft_tokens', 0)
        if drafted:
            # Accepted tokens / drafted tokens — the speculative win in
            # one number: every accepted token skipped a weight pass.
            self.telemetry['spec_accept_rate'] = round(
                self._stats.get('spec_accepted_tokens', 0) / drafted, 4
            )
        spec_windows = self._stats.get('spec_windows', 0)
        if spec_windows and loop_s > 0:
            self.telemetry['spec_windows_per_s'] = round(
                spec_windows / loop_s, 2
            )
        if self.kv_tier is not None:
            overlap = self.tier_summary().get('promotion_overlap')
            if overlap is not None:
                self.telemetry['tier_promotion_overlap'] = overlap
        if n_out:
            self.telemetry['overshoot_frac'] = round(
                self._stats.get('overshoot_tokens', 0) / n_out, 4
            )
        outs = []
        for rid in ids:
            request = self._finished.pop(rid)
            out = request.output_ids
            # Strip the stop token if present.
            eos = getattr(self.tokenizer, 'eos_id', None)
            stops = set(request.params.stop_token_ids)
            if eos is not None:
                stops.add(eos)
            if out and out[-1] in stops:
                out = out[:-1]
            outs.append(out)
        return outs

    def generate(
        self, prompts: list[str], params: SamplingParams | None = None
    ) -> list[str]:
        """Offline text API (vLLM ``llm.generate`` parity)."""
        batches = self.tokenizer(prompts)
        prompt_ids = [
            [int(t) for t, m in zip(row_ids, row_mask) if m]
            for row_ids, row_mask in zip(
                batches.input_ids, batches.attention_mask
            )
        ]
        outputs = self.generate_ids(prompt_ids, params)
        return [self.tokenizer.decode(out) for out in outputs]

    def shutdown(self) -> None:
        get_stall_watchdog().unwatch(self)
        if self._history_sampler is not None:
            self._history_sampler.stop()
            self._history_sampler = None
        if self._peer_kv_server is not None:
            self._peer_kv_server.close()
            self._peer_kv_server = None
        if self.kv_tier is not None and self.kv_tier.peer is not None:
            self.kv_tier.peer.close()
        self.params = None
        self.kv = None


def _gather_blocks_all_layers(k_cache, v_cache, block_ids):
    """Blocks ``block_ids`` of every layer of a stacked pool, ``[L, n,
    ...]`` a leaf. The gather names (layer, block) pairs: a slice over the
    layer axis (``c[:, block_ids]``) makes the TPU compiler move that axis
    of the whole head-folded pool inward first, a copy of the pool."""
    return jax.tree.map(
        lambda c: c[jnp.arange(c.shape[0])[:, None], block_ids[None, :]],
        (k_cache, v_cache),
    )


def _write_prefill_all_layers(
    k_cache, v_cache, k_seq, v_seq, block_rows, lengths
):
    """Scatter ``[L, B, S, N_kv * Hd]`` prefill K/V (rows already folded,
    as the engine's dense prefill program returns them) into the paged
    cache.

    ``block_rows`` is ``[B, R]`` and ``lengths`` ``[B]``; positions at or
    beyond a row's length (padding rows have length 0) write to the
    reserved trash block 0. A :class:`QuantizedKV` pool quantizes at this
    write (per-block-per-KV-head absmax over the live rows — full prefill
    always starts its blocks fresh, so this is single-shot quantization,
    no rescale chain).
    """
    num_layers, batch, seq_len = k_seq.shape[:3]
    quantized = isinstance(k_cache, QuantizedKV)
    block_size = (k_cache.data if quantized else k_cache).shape[2]
    positions = jnp.arange(seq_len)[None, :]  # [1, S]
    valid = positions < lengths[:, None]  # [B, S]
    block_ids = jnp.where(
        valid,
        jnp.take_along_axis(block_rows, positions // block_size, axis=1),
        0,
    )
    offsets = jnp.where(valid, positions % block_size, 0)
    # A row a (layer, block, offset): a window that spans the layer axis
    # as well (``.at[:, blocks, offsets]``) makes the TPU compiler move
    # the layer axis of the whole pool inward for the scatter and back, a
    # copy of the pool each way (0.67 GB of temporaries at mistral7b's
    # sizes).
    at = (
        jnp.arange(num_layers)[:, None],
        block_ids.reshape(1, -1),
        offsets.reshape(1, -1),
    )
    if quantized:
        return _write_prefill_all_layers_quantized(
            k_cache, v_cache, k_seq, v_seq, block_rows, lengths, valid, at
        )
    rows = (num_layers, batch * seq_len, -1)
    k_cache = k_cache.at[at].set(k_seq.reshape(rows).astype(k_cache.dtype))
    v_cache = v_cache.at[at].set(v_seq.reshape(rows).astype(v_cache.dtype))
    return k_cache, v_cache


def _write_prefill_all_layers_quantized(
    k_cache, v_cache, k_seq, v_seq, block_rows, lengths, valid, at
):
    """Quantized twin of :func:`_write_prefill_all_layers`.

    Every block this scatter touches is freshly owned by its row (full
    prefill from position 0), so each block's scale is its live rows'
    absmax / 127 computed in one masked pass — never a running-absmax
    rescale. Dead rows and dead blocks route to the trash block 0 with a
    zero scale, and ``quantize_kv_rows``'s guarded denominator keeps the
    dead branch finite (no NaN may reach a scatter, even into trash).
    """
    num_layers, batch, seq_len = k_seq.shape[:3]
    block_size = k_cache.data.shape[2]
    nt = -(-seq_len // block_size)  # blocks per row this shape can touch
    pad = nt * block_size - seq_len
    live_blk = jnp.arange(nt)[None, :] * block_size < lengths[:, None]
    phys = jnp.where(live_blk, block_rows[:, :nt], 0)  # [B, nt]
    flat_phys = phys.reshape(-1)

    def write_one(cache, seq):
        seq = unfold_heads(seq, cache.scale.shape[-1])  # a scale a KV head
        amax = jnp.max(jnp.abs(seq.astype(jnp.float32)), axis=-1)
        amax = jnp.where(valid[None, :, :, None], amax, 0.0)
        blk_amax = jnp.pad(
            amax, ((0, 0), (0, 0), (0, pad), (0, 0))
        ).reshape(num_layers, batch, nt, block_size, -1).max(axis=3)
        new_scale = blk_amax / KV_QUANT_MAX  # [L, B, nt, Nkv]
        scale = cache.scale.at[:, flat_phys].set(
            new_scale.reshape(num_layers, batch * nt, -1)
        )
        # Each token row quantizes against ITS block's scale.
        scale_tok = jnp.repeat(new_scale, block_size, axis=2)[:, :, :seq_len]
        q = quantize_kv_rows(seq, scale_tok)
        data = cache.data.at[at].set(
            q.reshape(num_layers, batch * seq_len, -1)
        )
        return QuantizedKV(data, scale)

    return write_one(k_cache, k_seq), write_one(v_cache, v_seq)
