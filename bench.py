"""Headline benchmarks: embeddings/sec/chip + generation tokens/sec/chip.

Prints ONE JSON line of the driver-contract shape::

    {"metric": "embeddings/sec/chip", "value": N, "unit": "emb/s",
     "vs_baseline": R, ...extra fields...}

Extra fields carry the second BASELINE.md metric (generation tokens/sec/chip
on a Mistral-7B-dims decoder through the continuous-batching engine), MFU
telemetry for both stages, and an ``error`` field per stage when a stage
fails — the driver always gets a parseable line, never a bare traceback.

Structure: ``python bench.py`` is an orchestrator that never touches JAX
(a parent that has touched JAX holds the chip, and a child that needs it
then fails or hangs). It probes the backend ONCE in a short child that has
exited before the first stage starts — the platform must be ``tpu`` unless
the caller asked for ``JAX_PLATFORMS=cpu`` — then runs each stage in its
own subprocess (``--stage embed`` / ``--stage gen``), one at a time, so an
OOM in one stage cannot take down the other, and composes the single
output line. The exit code is non-zero when the probe failed or any stage
ended in ``*_error``; the line is printed either way.

**Crash-proof contract (ISSUE 3 tentpole).** Rounds 3–5 all produced an
empty official record because this line was composed only after the LAST
stage. The orchestrator is now built around an incremental on-disk run
record and a global wall-clock deadline:

- every completed stage's JSON fragment is fsync'd to ``BENCH_partial.jsonl``
  (plus an atomically-rewritten ``BENCH_snapshot.json``) the moment the
  stage exits — a later crash can truncate coverage, never zero it;
- the deadline (``DISTLLM_BENCH_DEADLINE_S``, default 3300 s — safely under
  a 1 h driver timeout; the driver's ``timeout`` sends SIGTERM, rc 124)
  caps every per-stage budget, and a SIGALRM fires just before it
  expires;
- SIGTERM / SIGALRM / normal exit all emit the SAME driver-contract line,
  composed from whatever the run record holds — so an external kill still
  publishes every completed stage;
- stages run cheapest-first (embed → embed_q → gen → gen_prefix →
  gen_mixed → gen_spec → gen_kernel → gen_load → gen_tier → gen_chaos →
  gen_kvq → gen_q: embed warmups are minutes, ``gen_prefix``/
  ``gen_mixed``/``gen_spec``/``gen_load``/``gen_tier``/``gen_chaos`` and
  ``gen_kernel``'s XLA arm reuse ``gen``'s compile cache, ``gen_kvq``
  compiles its own block_size=32 bf16/int8 shapes, and int8 weight-quant
  ``gen_q``'s cold warmup — 22–45 min in round 4 — goes last);
- a failing or SIGTERM'd stage dumps a debug bundle (flight ring, metrics,
  traces — ``observability.dump_debug_bundle``) so a dead stage still
  explains itself, and gen stages run under a ``StallWatchdog``.

The reference publishes no numbers (BASELINE.md); ``vs_baseline`` ratios are
against analytic A100 estimates derived from the reference's production
configs, stated inline where computed. Zero egress: weights are random-init
at exact model dims (numerics are irrelevant to throughput) and the
tokenizer is the deterministic hash-vocab one.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

# ----------------------------------------------------------------- stages


def _workload_fingerprint(payload) -> str:
    """Stable 12-hex digest of a stage's full workload (prompts + params).

    Recorded in the bench JSON so any two runs claiming the same metric can
    be checked for actually measuring the same thing (round 2 vs round 3
    reported 795 vs 605 tok/s on what turned out to be different prompt
    sets — this makes such drift visible instead of mysterious).
    """
    import hashlib

    blob = json.dumps(payload, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def _cache_entries() -> int | None:
    """Entries in the compilation cache directory jax is configured with
    (None if it doesn't exist yet). before/after deltas reveal whether
    warmup compiles HIT the cache or re-lowered everything."""
    from distllm_tpu.utils import compile_cache_entries

    return compile_cache_entries()


def _cache_fields(prefix: str, cache_before: int | None) -> dict:
    """Compile-cache evidence for the stage: a warmed serve start must
    compile NOTHING (vLLM has no multi-minute unrolled-window compile to
    hide; our persistent cache is what matches that). ``warm_start`` is
    the claim checked across back-to-back bench runs: run 1 may populate,
    run 2 must show delta 0. DISTLLM_BENCH_REQUIRE_WARM=1 turns a cold
    start into a hard failure (CI on a preflight-seeded cache)."""
    cache_after = _cache_entries()
    delta = (
        cache_after - cache_before
        if cache_after is not None and cache_before is not None
        else None
    )
    if os.environ.get('DISTLLM_BENCH_REQUIRE_WARM'):
        if delta is None:
            raise RuntimeError(
                f'{prefix}stage: DISTLLM_BENCH_REQUIRE_WARM set but the '
                'compilation cache dir is missing — nothing can be warm '
                '(seed with scripts/aot_preflight.py first)'
            )
        if delta > 0:
            raise RuntimeError(
                f'{prefix}stage compiled {delta} new cache entries on a '
                'cache expected warm (seed with scripts/aot_preflight.py '
                'first)'
            )
    return {
        f'{prefix}cache_entries_before': cache_before,
        f'{prefix}cache_entries_after': cache_after,
        f'{prefix}warm_start': delta == 0 if delta is not None else None,
    }


def _stage_embed(quantization: str | None = None, prefix: str = '') -> dict:
    """Embed pipeline hot loop: bucketed tokenize -> jitted bf16 BERT
    forward -> mean pool -> host copy. PubMedBERT dims
    (microsoft/S-PubMedBert-MS-MARCO = BERT-base), reference production
    batch 512 (ref README.md:65). ``quantization='int8'`` measures the
    weight-only quantized encoder (the TPU stand-in for the reference's
    NF4 load path, embed/encoders/auto.py:46-56)."""
    import jax
    import numpy as np

    from distllm_tpu.embed import get_pooler
    from distllm_tpu.embed.embedders.full_sequence import compute_embeddings
    from distllm_tpu.embed.encoders.base import JaxEncoder
    from distllm_tpu.models import bert
    from distllm_tpu.models.tokenizer import WhitespaceTokenizer

    rng = np.random.default_rng(0)

    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        # Smoke-test dims for CPU CI; real runs use PubMedBERT dims.
        cfg = bert.BertConfig(
            vocab_size=2048, hidden_size=128, num_layers=2, num_heads=4,
            intermediate_size=256, max_position_embeddings=512,
            dtype='float32',
        )
    else:
        cfg = bert.BertConfig(
            vocab_size=30522,
            hidden_size=768,
            num_layers=12,
            num_heads=12,
            intermediate_size=3072,
            max_position_embeddings=512,
            dtype='bfloat16',
        )
    params = bert.init(jax.random.PRNGKey(0), cfg)
    tokenizer = WhitespaceTokenizer(vocab_size=cfg.vocab_size, model_max_length=512)
    encoder = JaxEncoder(
        config=None,
        apply_fn=bert.apply,
        model_cfg=cfg,
        params=jax.device_put(params),
        tokenizer=tokenizer,
        embedding_size=cfg.hidden_size,
        quantization=quantization,
    )
    pooler = get_pooler({'name': 'mean'})

    batch_size = 64 if small else 512
    # Chunk-sized texts (~150-250 'words') like jsonl_chunk buffers.
    vocab = [f'tok{i}' for i in range(5000)]
    texts = []
    for _ in range(128 if small else 2048):
        n = int(rng.integers(120, 260))
        texts.append(' '.join(rng.choice(vocab, size=n)))

    # Warmup compiles every bucket shape the sorted batches touch.
    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    compute_embeddings(texts, encoder, pooler, batch_size)
    jax.block_until_ready(encoder.params)
    warmup_secs = time.perf_counter() - warmup_start
    bucket_stats: dict = {}
    start = time.perf_counter()
    out = compute_embeddings(
        texts, encoder, pooler, batch_size, stats=bucket_stats
    )
    elapsed = time.perf_counter() - start
    assert out.shape == (len(texts), cfg.hidden_size)
    throughput = len(texts) / elapsed

    # Analytic A100 estimate: 2 * n_params * 256 tokens/seq FLOPs at
    # 312 TF/s bf16 peak * 50% MFU. n_params comes from the actual config
    # (110M at PubMedBERT dims) so the small smoke mode reports honest
    # ratios instead of constants sized for the full model.
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    tokens_per_seq = 256
    flops_per_seq = 2 * n_params * tokens_per_seq
    a100_estimate = (312e12 * 0.50) / flops_per_seq

    peak = _chip_peak_flops(jax.devices()[0])
    mfu = throughput * flops_per_seq / peak if peak else None
    out = {
        f'{prefix}metric': 'embeddings/sec/chip',
        f'{prefix}value': round(throughput, 2),
        f'{prefix}unit': 'emb/s',
        f'{prefix}vs_baseline': round(throughput / a100_estimate, 3),
        f'{prefix}mfu': round(mfu, 3) if mfu is not None else None,
        f'{prefix}device': str(jax.devices()[0].device_kind),
        f'{prefix}workload': _workload_fingerprint(
            {'texts': texts, 'batch_size': batch_size,
             'dims': cfg.model_dump() if hasattr(cfg, 'model_dump') else str(cfg)}
        ),
        f'{prefix}warmup_secs': round(warmup_secs, 1),
        **_cache_fields(prefix, cache_before),
        f'{prefix}padding_frac': round(
            1 - bucket_stats['tokens_real'] / bucket_stats['tokens_padded'], 3
        ),
        f'{prefix}bucket_batches': {
            str(k): v
            for k, v in sorted(bucket_stats['bucket_batches'].items())
        },
    }
    if quantization:
        out[f'{prefix}quantization'] = quantization
    return out


def _measure_load_ttft(engine, prompts, probe_prompt, sampling,
                       probe_sampling) -> float | None:
    """TTFT of a request injected while the engine is mid-stream at full
    decode batch (``gen_load_ttft_s``) — the interference number mixed
    batching exists to improve: standalone prefill dispatches serialize
    between decode windows (scripts/probe_gen.py looks at this), so a request
    arriving under load pays its prefill AGAINST the running stream.

    Saturates the batch via ``step()``, waits until every slot is
    actively decoding, injects one probe request, and reads its
    first-token latency off the request-lifecycle timestamps.
    """
    from distllm_tpu.generate.engine.engine import RequestState

    for prompt in prompts:
        engine.add_request(prompt, sampling)
    probe_rid = None
    while engine.has_unfinished:
        engine.step()
        if probe_rid is not None:
            continue
        running = [
            r for r in engine._requests.values()
            if r.state is RequestState.RUNNING
        ]
        if len(running) >= min(
            len(prompts), engine.config.max_num_seqs
        ) and all(r.output_ids for r in running):
            probe_rid = engine.add_request(probe_prompt, probe_sampling)
    if probe_rid is None:
        return None
    probe = engine._finished.pop(probe_rid, None)
    if probe is None or not probe.t_first_token:
        return None
    return probe.t_first_token - probe.t_enqueue


def _run_gen(quantization: str | None, prefix: str) -> dict:
    """Generation through the continuous-batching engine at Mistral-7B dims
    (random weights on device; numerics irrelevant to throughput).

    Workload shape follows the reference's production serving pattern
    (mixed prompt lengths; ref examples/miscellaneous/
    multi_gpu_batch_config.yaml: max_num_seqs 128, client batch 16;
    sampling defaults ref vllm_backend.py:19-27). bf16 serving fits
    max_num_seqs=32 beside 13.5 GiB of weights on a 16 GiB v5e; int8
    weight-only quantization (the TPU answer to the reference's NF4 HF
    path, huggingface_backend.py:66-77) halves weight HBM and runs the
    reference's full max_num_seqs=128."""
    import jax
    import numpy as np

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.models import mistral

    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        # Smoke-test dims for CPU CI; real runs use the 7B defaults.
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
    n_params = sum(
        int(np.prod(x.shape))
        for x in jax.tree.leaves(
            jax.eval_shape(
                lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg)
            )
        )
    )

    if quantization is None:
        # bf16: 13.5 GiB weights + 32 seqs x 22 blocks x 2 MiB = 1.4 GiB KV.
        max_num_seqs, num_blocks, n_prompts = 32, 712, 96
    else:
        # int8: ~7 GiB weights frees HBM for the reference's production
        # batch (max_num_seqs 128).
        max_num_seqs, num_blocks, n_prompts = 128, 2840, 320
    # A/B toggle for mixed prefill+decode windows (docs/serving.md):
    # DISTLLM_BENCH_MIXED=1 serves this stage with prefill chunks riding
    # decode windows; the dedicated gen_mixed stage runs the token-
    # identity A/B either way.
    mixed = os.environ.get('DISTLLM_BENCH_MIXED', '') not in ('', '0')
    engine_cfg = EngineConfig(
        block_size=16,
        num_blocks=num_blocks,
        max_num_seqs=max_num_seqs,
        max_model_len=512,
        decode_steps=16,
        pipeline_depth=2,
        quantization=quantization,
        # Serving fast path: top-64 sampling window instead of a 32k-vocab
        # sort per decode step (exact top-p within the window).
        sampling_top_window=64,
        enable_mixed_batching=mixed,
        max_window_prefill_tokens=256,
        # Only paged-route tails ride windows; chunking is what puts this
        # stage's fresh 32-192-token prompts on that route when the
        # toggle is on. Off keeps the classic batched dense prefill.
        prefill_chunk_tokens=64 if mixed else 0,
    )
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, model_cfg.vocab_size, size=int(n)))
        for n in rng.integers(32, 192, size=n_prompts)
    ]
    gen_tokens = 128
    sampling = SamplingParams(
        temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=gen_tokens
    )

    # engine.warmup() compiles every (batch, bucket) prefill shape, the KV
    # scatter, the fused decode window, and the samplers outside the timed
    # region; the persistent compilation cache (enabled in main) makes
    # repeat runs start hot. jax.jit is lazy, so an unavailable Pallas
    # lowering only surfaces here, and fails the stage (_build_engine).
    def make_params():
        if quantization is not None and jax.default_backend() != 'cpu':
            # Quantize on the HOST cpu device and ship only the codes:
            # letting the engine quantize device-resident bf16 moves
            # 14.5 GB device-to-host and 7.25 GB back. The engine
            # passes pre-quantized QTensor leaves through untouched.
            import ml_dtypes

            from distllm_tpu.ops.quantization import quantize_pytree

            shapes = jax.eval_shape(
                lambda: mistral.init_on_device(
                    jax.random.PRNGKey(0), model_cfg
                )
            )
            host_rng = np.random.default_rng(0)
            np_dtype = {
                'bfloat16': ml_dtypes.bfloat16, 'float32': np.float32,
            }[model_cfg.dtype]

            def _host_leaf(leaf):
                return (
                    host_rng.standard_normal(leaf.shape, dtype=np.float32)
                    * 0.02
                ).astype(np_dtype)

            qtree = quantize_pytree(
                jax.tree.map(_host_leaf, shapes),
                mode=quantization,
                out_dtype=model_cfg.dtype,
            )
            return jax.device_put(qtree, jax.devices()[0])
        return mistral.init_on_device(jax.random.PRNGKey(0), model_cfg)

    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    engine = _build_engine(
        model_cfg,
        engine_cfg,
        make_params,
        prompts[:2],
        SamplingParams(temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=4),
    )
    warmup_secs = time.perf_counter() - warmup_start

    # Time-to-first-token on the WARMED engine: one prompt, one token —
    # prefill dispatch + first decode window + host sync. This is the
    # serving latency a vLLM user compares against; on a warm compile
    # cache it must be free of compile time (see warm_start below).
    ttft_start = time.perf_counter()
    engine.generate_ids(
        prompts[:1],
        SamplingParams(temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=1),
    )
    ttft_s = time.perf_counter() - ttft_start

    # TTFT *under load*: inject a request while the engine is mid-stream
    # at full decode batch (gen_load_ttft_s, next to gen_ttft_s). This is
    # the interference number mixed batching must improve — the idle-
    # engine ttft_s above cannot see prefill/decode serialization.
    load_ttft_s = _measure_load_ttft(
        engine,
        prompts[: min(max_num_seqs, len(prompts))],
        prompts[-1],
        SamplingParams(
            temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=32
        ),
        SamplingParams(
            temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=2
        ),
    )

    # DISTLLM_BENCH_PROFILE=<dir> wraps the timed region in a profiler
    # trace (XPlane + TensorBoard format): on hardware this shows per-op
    # device time for the decode windows — the ground truth the AOT HLO
    # census (scripts/probe_decode_hlo.py) can only approximate. Routed
    # through the bounded capture helper (observability/profiling.py):
    # an unsupported-backend profiler error downgrades to a fragment
    # field instead of killing the stage, and a hung region cannot leave
    # the trace growing forever.
    profile_dir = os.environ.get('DISTLLM_BENCH_PROFILE')
    capture = None
    if profile_dir:
        from distllm_tpu.observability.profiling import get_profiler_capture

        capture = get_profiler_capture()
        if not capture.start(profile_dir, max_seconds=1800.0):
            capture = None
    try:
        start = time.perf_counter()
        outs = engine.generate_ids(prompts, sampling)
        elapsed = time.perf_counter() - start
    finally:
        # Flush even when generation dies mid-decode — a partial trace of
        # the failing run is exactly what the profile exists to capture.
        if capture is not None:
            capture.stop()
    n_tokens = sum(len(o) for o in outs)
    throughput = n_tokens / elapsed

    # Analytic A100 estimate for decode of this model: the roofline is
    # min(compute, HBM bandwidth). At these batches decode is
    # weight-bandwidth bound: tokens/s ~= batch * BW_eff / model_bytes with
    # A100-80GB 2.0e12 B/s at 60% efficiency and bf16 weights — i.e. the
    # reference's own vLLM serving dtype at the SAME concurrency. (Per
    # chip, an A100 has 2.4x the HBM bandwidth and 1.6x the bf16 FLOPs of
    # a v5e, so ratios compare silicon, not software.)
    flops_per_token = 2 * n_params
    model_bytes = 2 * n_params
    a100_bw_bound = max_num_seqs * (2.0e12 * 0.60) / model_bytes
    a100_compute_bound = (312e12 * 0.50) / flops_per_token
    a100_estimate = min(a100_bw_bound, a100_compute_bound)

    peak = _chip_peak_flops(jax.devices()[0])
    mfu = throughput * flops_per_token / peak if peak else None
    out = {
        f'{prefix}metric': 'gen tokens/sec/chip',
        f'{prefix}value': round(throughput, 2),
        f'{prefix}unit': 'tok/s',
        f'{prefix}vs_baseline': round(throughput / a100_estimate, 3),
        f'{prefix}mfu': round(mfu, 4) if mfu is not None else None,
        f'{prefix}n_tokens': n_tokens,
        f'{prefix}attn_backend': engine.telemetry['attn_backend'],
        f'{prefix}batch': max_num_seqs,
        f'{prefix}decode_steps': engine_cfg.decode_steps,
        f'{prefix}scheduler_impl': type(engine.sched).__name__,
        f'{prefix}workload': _workload_fingerprint(
            {'prompts': [list(map(int, p)) for p in prompts],
             'sampling': sampling.__dict__,
             'engine': {'block_size': engine_cfg.block_size,
                        'num_blocks': num_blocks,
                        'max_num_seqs': max_num_seqs,
                        'decode_steps': engine_cfg.decode_steps},
             'gen_tokens': gen_tokens}
        ),
        f'{prefix}warmup_secs': round(warmup_secs, 1),
        f'{prefix}ttft_s': round(ttft_s, 3),
        f'{prefix}load_ttft_s': (
            round(load_ttft_s, 3) if load_ttft_s is not None else None
        ),
        f'{prefix}mixed_batching': mixed,
        **_cache_fields(prefix, cache_before),
    }
    if quantization:
        out[f'{prefix}quantization'] = quantization
    if profile_dir and capture is None:
        # The profiler was requested but could not start (unsupported
        # backend, busy slot): the stage ran unprofiled and says so.
        from distllm_tpu.observability.profiling import get_profiler_capture

        out[f'{prefix}profile_error'] = (
            get_profiler_capture().state().get('last_error')
        )
    for key, val in engine.telemetry.items():
        out[f'{prefix}{key}'] = val
    return out


def _build_engine(
    model_cfg, engine_cfg, make_params, smoke_prompts, smoke_params
):
    """Build, warm and smoke-run the serving engine with
    ``attn_backend='auto'`` (the fused Pallas kernel on the chip, XLA on
    the CPU test tier). jax.jit is lazy, so a kernel that fails to
    compile surfaces at warmup — and fails the stage: there is no second
    backend to fall to, a run that reports a number ran the kernel. One
    home for the build so the gen stages cannot drift on it.
    """
    from distllm_tpu.generate.engine.engine import LLMEngine

    class _Tok:
        eos_id = None

    engine_cfg.attn_backend = 'auto'
    # The engine owns (and may delete) the params for destructive HBM
    # optimizations (relayout, quant cleanup).
    engine = LLMEngine(
        model_cfg, make_params(), _Tok(), engine_cfg, own_params=True
    )
    try:
        engine.warmup()
        engine.generate_ids(smoke_prompts, smoke_params)
    except Exception:
        engine.shutdown()  # free HBM before the stage's error is reported
        raise
    return engine


def _stage_gen_prefix() -> dict:
    """Prefix-caching serving stage (docs/prefix_caching.md): repeated
    shared-prefix prompts — the RAG-chat / MCQA shape where every request
    repeats a long system-prompt/stem and differs only in a short tail.

    Records ``gen_prefix_ttft_s`` (warm TTFT with the prefix cached — the
    number prefix caching exists to shrink), the cold TTFT baseline on the
    SAME engine, cache hit rate, and throughput over the full workload.
    """
    import jax
    import numpy as np

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.models import mistral

    prefix = 'gen_prefix_'
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults

    engine_cfg = EngineConfig(
        block_size=16,
        num_blocks=712,
        max_num_seqs=32,
        max_model_len=512,
        decode_steps=16,
        pipeline_depth=2,
        sampling_top_window=64,
        enable_prefix_cache=True,
        prefill_chunk_tokens=256,
    )
    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    engine = _build_engine(
        model_cfg,
        engine_cfg,
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
        [[1, 2, 3]],
        SamplingParams(temperature=0.0, max_tokens=2),
    )
    warmup_secs = time.perf_counter() - warmup_start

    # Workload: one 320-token shared prefix (20 blocks), 32 requests with
    # distinct 16-token tails — the round-5 RAG serving shape.
    rng = np.random.default_rng(0)
    shared = list(rng.integers(1, model_cfg.vocab_size, size=320))
    prompts = [
        shared + list(rng.integers(1, model_cfg.vocab_size, size=16))
        for _ in range(32)
    ]
    one_token = SamplingParams(
        temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=1
    )
    # Cold TTFT: nothing cached, full 336-token prefill.
    t0 = time.perf_counter()
    engine.generate_ids(prompts[:1], one_token)
    ttft_cold_s = time.perf_counter() - t0
    # Warm TTFT: the 320-token prefix is cached; prefill covers the tail.
    t0 = time.perf_counter()
    engine.generate_ids(prompts[1:2], one_token)
    ttft_warm_s = time.perf_counter() - t0

    sampling = SamplingParams(
        temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=64
    )
    start = time.perf_counter()
    outs = engine.generate_ids(prompts[2:], sampling)
    elapsed = time.perf_counter() - start
    n_tokens = sum(len(o) for o in outs)
    out = {
        f'{prefix}metric': 'warm shared-prefix TTFT',
        f'{prefix}ttft_s': round(ttft_warm_s, 3),
        f'{prefix}ttft_cold_s': round(ttft_cold_s, 3),
        f'{prefix}ttft_speedup': round(ttft_cold_s / max(ttft_warm_s, 1e-9), 2),
        f'{prefix}throughput_tok_s': round(n_tokens / elapsed, 2),
        f'{prefix}n_tokens': n_tokens,
        f'{prefix}attn_backend': engine.telemetry['attn_backend'],
        f'{prefix}shared_prefix_tokens': len(shared),
        f'{prefix}warmup_secs': round(warmup_secs, 1),
        f'{prefix}workload': _workload_fingerprint(
            {'prompts': [list(map(int, p)) for p in prompts],
             'sampling': sampling.__dict__,
             'engine': {'block_size': engine_cfg.block_size,
                        'num_blocks': engine_cfg.num_blocks,
                        'max_num_seqs': engine_cfg.max_num_seqs,
                        'prefill_chunk_tokens':
                            engine_cfg.prefill_chunk_tokens}}
        ),
        **_cache_fields(prefix, cache_before),
    }
    for key, val in engine.telemetry.items():
        out[f'{prefix}{key}'] = val
    return out


def _stage_gen_mixed() -> dict:
    """Mixed serving-window A/B (docs/serving.md): the SAME staggered
    serving workload with ``enable_mixed_batching`` off, then on.

    The contract this stage checks and records:

    - greedy output tokens are BIT-IDENTICAL between the arms;
    - the on arm folds prefill chunks into decode windows (``mixed``
      flight records present, standalone prefill dispatch count strictly
      lower than the off arm);
    - both arms record the mid-stream ``load_ttft`` interference number
      (the idle-engine TTFT cannot see prefill/decode serialization).

    The workload staggers finish budgets so slots free while neighbours
    still decode — mid-stream admission is what rides windows; a uniform
    batch that drains all slots at once never exercises the fold.
    """
    import jax
    import numpy as np

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.models import mistral
    from distllm_tpu.observability.flight import get_flight_recorder

    prefix = 'gen_mixed_'
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
        max_num_seqs, num_blocks = 4, 160
        n_prompts, prompt_lo, prompt_hi = 12, 8, 48
        budget, chunk, out_lo, out_hi = 16, 16, 4, 24
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        max_num_seqs, num_blocks = 32, 712
        n_prompts, prompt_lo, prompt_hi = 64, 32, 192
        budget, chunk, out_lo, out_hi = 256, 256, 16, 96

    rng = np.random.default_rng(0)
    # Every third prompt repeats a 2-block shared prefix (the RAG/MCQA
    # shape): its cached-prefix tail is a paged-route span that rides
    # windows; the long fresh prompts ride through chunked tails.
    shared = list(rng.integers(1, model_cfg.vocab_size, size=32))
    prompts = []
    for i, n in enumerate(rng.integers(prompt_lo, prompt_hi, size=n_prompts)):
        tail = list(rng.integers(1, model_cfg.vocab_size, size=int(n)))
        prompts.append(shared + tail if i % 3 == 0 else tail)
    budgets = [int(n) for n in rng.integers(out_lo, out_hi, size=n_prompts)]
    # The load-TTFT probe must be a NEVER-SEEN prompt: by probe time the
    # main A/B run has adopted every workload prompt's full blocks into
    # the per-engine prefix cache, and a cached probe would measure a
    # ~1-token COW admission instead of prefill-under-load interference.
    probe_prompt = list(
        rng.integers(1, model_cfg.vocab_size, size=prompt_hi)
    )

    def run_arm(mixed: bool) -> dict:
        engine_cfg = EngineConfig(
            block_size=16,
            num_blocks=num_blocks,
            max_num_seqs=max_num_seqs,
            max_model_len=512,
            decode_steps=16,
            pipeline_depth=2,
            sampling_top_window=64,
            enable_prefix_cache=True,
            prefill_chunk_tokens=chunk,
            enable_mixed_batching=mixed,
            max_window_prefill_tokens=budget,
        )
        engine = _build_engine(
            model_cfg,
            engine_cfg,
            lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
            [[1, 2, 3]],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        flight_before = sum(
            1 for r in get_flight_recorder().snapshot()
            if r['kind'] == 'mixed'
        )
        rids = [
            engine.add_request(
                p, SamplingParams(temperature=0.0, max_tokens=n)
            )
            for p, n in zip(prompts, budgets)
        ]
        start = time.perf_counter()
        seen: dict = {rid: [] for rid in rids}
        while engine.has_unfinished:
            for rid, tok in engine.step():
                seen[rid].append(tok)
        elapsed = time.perf_counter() - start
        n_tokens = sum(len(v) for v in seen.values())
        load_ttft_s = _measure_load_ttft(
            engine,
            prompts[:max_num_seqs],
            probe_prompt,
            SamplingParams(temperature=0.0, max_tokens=32),
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        arm = {
            'tokens': [seen[rid] for rid in rids],
            'throughput_tok_s': round(n_tokens / elapsed, 2),
            'prefill_dispatches': int(
                engine._stats.get('prefill_dispatches', 0)
            ),
            'mixed_windows': int(engine._stats.get('mixed_windows', 0)),
            'mixed_prefill_tokens': int(
                engine._stats.get('mixed_prefill_tokens', 0)
            ),
            'mixed_flight_records': sum(
                1 for r in get_flight_recorder().snapshot()
                if r['kind'] == 'mixed'
            ) - flight_before,
            'load_ttft_s': (
                round(load_ttft_s, 3) if load_ttft_s is not None else None
            ),
        }
        engine.shutdown()
        return arm

    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    off = run_arm(False)
    on = run_arm(True)
    warmup_secs = time.perf_counter() - warmup_start
    identical = on['tokens'] == off['tokens']
    out = {
        f'{prefix}metric': 'mixed-window A/B',
        f'{prefix}tokens_identical': identical,
        f'{prefix}throughput_tok_s': on['throughput_tok_s'],
        f'{prefix}off_throughput_tok_s': off['throughput_tok_s'],
        f'{prefix}load_ttft_s': on['load_ttft_s'],
        f'{prefix}off_load_ttft_s': off['load_ttft_s'],
        f'{prefix}prefill_dispatches': on['prefill_dispatches'],
        f'{prefix}off_prefill_dispatches': off['prefill_dispatches'],
        f'{prefix}windows': on['mixed_windows'],
        f'{prefix}prefill_tokens_ridden': on['mixed_prefill_tokens'],
        f'{prefix}flight_records': on['mixed_flight_records'],
        f'{prefix}off_flight_records': off['mixed_flight_records'],
        f'{prefix}elapsed_both_arms_s': round(warmup_secs, 1),
        f'{prefix}workload': _workload_fingerprint(
            {'prompts': [list(map(int, p)) for p in prompts],
             'budgets': budgets,
             'engine': {'max_num_seqs': max_num_seqs,
                        'num_blocks': num_blocks,
                        'max_window_prefill_tokens': budget,
                        'prefill_chunk_tokens': chunk}}
        ),
        **_cache_fields(prefix, cache_before),
    }
    if not identical:
        out[f'{prefix}error'] = (
            'mixed on/off token mismatch — the A/B identity contract is '
            'broken'
        )
    return out


def _stage_gen_spec() -> dict:
    """Prompt-lookup speculative decoding A/B (docs/speculative.md): the
    SAME staggered workload through the greedy arms — the classic
    decode scan (``draft_k=0``), verify windows with drafting disabled
    (``spec_draft_source='none'``), and full speculation — plus a
    sampled (temperature > 0) arm run twice for determinism evidence.

    The contract this stage checks and records:

    - drafting on vs off INSIDE the verify kernel is BIT-IDENTICAL
      (``tokens_identical`` — same compiled executable, so this holds in
      bf16; a mismatch means the acceptance rule or rollback is broken
      and the stage records an error);
    - agreement with the classic decode-scan arm is recorded as
      ``tokens_match_decode_path``: guaranteed only in fp32 — two
      compiled programs may round a near-tied bf16 logit differently
      (measured: a 3.9e-3 top-2 gap flipped on CPU smoke), the same
      reason vLLM does not promise bitwise spec parity — so it is
      evidence, not an assert;
    - ``gen_spec_accept_rate`` — accepted / drafted tokens, the
      speculative win in one number (every accepted token skipped its
      weight pass) — and tok/s for all arms, comparable to
      ``gen_tok_per_s``;
    - verify windows actually ran (``spec_windows`` > 0);
    - the SAMPLED arm (``gen_spec_sampled_*``): the same workload at
      temperature > 0 with explicit per-request seeds rides the verify
      kernel through device-side rejection sampling
      (docs/speculative.md "Sampled verification"). Run twice —
      ``sampled_deterministic`` is the (seed, schedule) determinism
      evidence, and ``sampled_accepted_tokens`` must be > 0 (the stage
      records an error otherwise). ``sampled_accept_rate`` gates
      higher-better in benchdiff.

    ``DISTLLM_BENCH_SPEC=0`` skips the stage (default on). The workload
    is deliberately repetitive — shared prefixes plus prompts that
    repeat an n-gram motif, the RAG-quote/MCQA-stem shape prompt lookup
    exploits.
    """
    import jax
    import numpy as np

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.models import mistral

    prefix = 'gen_spec_'
    if os.environ.get('DISTLLM_BENCH_SPEC', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_SPEC=0'}
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
        max_num_seqs, num_blocks = 4, 160
        n_prompts, prompt_lo, prompt_hi = 12, 8, 48
        out_lo, out_hi, draft_k = 4, 24, 4
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        max_num_seqs, num_blocks = 32, 712
        n_prompts, prompt_lo, prompt_hi = 64, 32, 192
        out_lo, out_hi, draft_k = 16, 96, 4

    rng = np.random.default_rng(0)
    shared = list(rng.integers(1, model_cfg.vocab_size, size=32))
    motif = list(rng.integers(1, model_cfg.vocab_size, size=8))
    prompts = []
    for i, n in enumerate(rng.integers(prompt_lo, prompt_hi, size=n_prompts)):
        tail = list(rng.integers(1, model_cfg.vocab_size, size=int(n)))
        if i % 2 == 0:
            # Tile the motif through the tail so the prompt itself holds
            # repeated n-grams — prompt-lookup's draft material.
            tail = (motif * (1 + len(tail) // len(motif)))[: len(tail)]
        prompts.append(shared + tail if i % 3 == 0 else tail)
    budgets = [int(n) for n in rng.integers(out_lo, out_hi, size=n_prompts)]

    def run_arm(
        k: int,
        source: str = 'prompt_lookup',
        temperature: float = 0.0,
        top_p: float = 1.0,
    ) -> dict:
        engine_cfg = EngineConfig(
            block_size=16,
            num_blocks=num_blocks,
            max_num_seqs=max_num_seqs,
            max_model_len=512,
            decode_steps=16,
            pipeline_depth=2,
            sampling_top_window=64,
            enable_prefix_cache=True,
            draft_k=k,
            spec_draft_source=source,
        )
        engine = _build_engine(
            model_cfg,
            engine_cfg,
            lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
            [[1, 2, 3]],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        rids = [
            engine.add_request(
                p,
                SamplingParams(
                    temperature=temperature,
                    top_p=top_p,
                    max_tokens=n,
                    # Explicit per-request seed: the sampled arm's output
                    # must be a pure function of (seed, schedule) so two
                    # runs give determinism evidence, not a coin flip.
                    seed=(1000 + i) if temperature > 0 else None,
                ),
            )
            for i, (p, n) in enumerate(zip(prompts, budgets))
        ]
        start = time.perf_counter()
        seen: dict = {rid: [] for rid in rids}
        while engine.has_unfinished:
            for rid, tok in engine.step():
                seen[rid].append(tok)
        elapsed = time.perf_counter() - start
        n_tokens = sum(len(v) for v in seen.values())
        drafted = int(engine._stats.get('spec_draft_tokens', 0))
        accepted = int(engine._stats.get('spec_accepted_tokens', 0))
        arm = {
            'tokens': [seen[rid] for rid in rids],
            'throughput_tok_s': round(n_tokens / elapsed, 2),
            'spec_windows': int(engine._stats.get('spec_windows', 0)),
            'draft_tokens': drafted,
            'accepted_tokens': accepted,
            'accept_rate': round(accepted / drafted, 4) if drafted else None,
        }
        engine.shutdown()
        return arm

    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    classic = run_arm(0)
    null = run_arm(draft_k, source='none')
    on = run_arm(draft_k)
    # Sampled arm (docs/speculative.md "Sampled verification"): the same
    # workload at temperature > 0 with explicit per-request seeds, run
    # TWICE for determinism evidence. Low temperature keeps the filtered
    # target sharp, so prompt-lookup drafts (point-mass q) are accepted
    # with high probability — the accepted > 0 contract is robust, not a
    # fluke of a flat random-weights distribution.
    sampled_temp, sampled_top_p = 0.15, 0.95
    sampled = run_arm(draft_k, temperature=sampled_temp, top_p=sampled_top_p)
    sampled_again = run_arm(
        draft_k, temperature=sampled_temp, top_p=sampled_top_p
    )
    warmup_secs = time.perf_counter() - warmup_start
    identical = on['tokens'] == null['tokens']
    matches_decode = on['tokens'] == classic['tokens']
    sampled_deterministic = sampled['tokens'] == sampled_again['tokens']
    out = {
        f'{prefix}metric': 'speculative-decoding A/B',
        f'{prefix}tokens_identical': identical,
        f'{prefix}tokens_match_decode_path': matches_decode,
        f'{prefix}tok_per_s': on['throughput_tok_s'],
        f'{prefix}off_tok_per_s': classic['throughput_tok_s'],
        f'{prefix}nodraft_tok_per_s': null['throughput_tok_s'],
        f'{prefix}accept_rate': on['accept_rate'],
        f'{prefix}windows': on['spec_windows'],
        f'{prefix}draft_tokens': on['draft_tokens'],
        f'{prefix}accepted_tokens': on['accepted_tokens'],
        f'{prefix}sampled_tok_per_s': sampled['throughput_tok_s'],
        f'{prefix}sampled_accept_rate': sampled['accept_rate'],
        f'{prefix}sampled_accepted_tokens': sampled['accepted_tokens'],
        f'{prefix}sampled_windows': sampled['spec_windows'],
        f'{prefix}sampled_deterministic': sampled_deterministic,
        f'{prefix}sampled_temperature': sampled_temp,
        f'{prefix}sampled_top_p': sampled_top_p,
        f'{prefix}draft_k': draft_k,
        f'{prefix}elapsed_all_arms_s': round(warmup_secs, 1),
        f'{prefix}workload': _workload_fingerprint(
            {'prompts': [list(map(int, p)) for p in prompts],
             'budgets': budgets,
             'engine': {'max_num_seqs': max_num_seqs,
                        'num_blocks': num_blocks,
                        'draft_k': draft_k}}
        ),
        **_cache_fields(prefix, cache_before),
    }
    if not identical:
        out[f'{prefix}error'] = (
            'speculation on/off token mismatch inside the verify kernel '
            '— the acceptance/rollback identity contract is broken'
        )
    elif on['spec_windows'] == 0:
        # Without verify windows the spec arms silently degenerate to the
        # classic path and every assertion above passes vacuously.
        out[f'{prefix}error'] = (
            'no speculative verify windows ran — draft_k routing is '
            'broken or the workload never decoded'
        )
    elif not sampled_deterministic:
        out[f'{prefix}error'] = (
            'sampled spec arm is nondeterministic across identical '
            '(seed, schedule) runs — the counter-based PRNG contract '
            '(docs/speculative.md "Sampled verification") is broken'
        )
    elif sampled['accepted_tokens'] == 0:
        out[f'{prefix}error'] = (
            'sampled spec arm accepted zero draft tokens — rejection '
            'sampling is discarding every draft, so temperature > 0 '
            'requests get no speculative win'
        )
    if not matches_decode:
        # Expected occasionally in bf16 (near-tie rounding across two
        # compiled programs, see the stage docstring); never in fp32.
        out[f'{prefix}decode_path_note'] = (
            'spec stream diverged from the classic decode-scan stream: '
            'bf16 near-tie across kernels (docs/speculative.md), not an '
            'acceptance bug — tokens_identical is the contract assert'
        )
    return out


def _stage_gen_kernel() -> dict:
    """Attention-kernel A/B (docs/serving.md "Attention kernel backends"):
    the SAME staggered greedy serving workload with ``attn_backend``
    pinned to 'xla', then to the fused ragged Pallas kernel ('pallas' on
    TPU; 'interpret' — the same kernel on the Pallas interpreter — for
    the CPU smoke).

    The contract this stage checks and records:

    - tok/s per arm (``gen_kernel_xla_tok_s`` /
      ``gen_kernel_pallas_tok_s``) and their ratio
      (``gen_kernel_speedup``);
    - MEASURED MFU / bandwidth utilization per arm (mean of the
      per-window ``mfu_measured``/``bw_util_measured`` flight fields —
      ``compiled.cost_analysis()`` truth, docs/observability.md) next to
      the analytic roofline pair, so a kernel win shows up as measured
      bytes down with tokens/s up and the benchdiff gate can hold the
      trajectory;
    - greedy token agreement across arms (``tokens_identical``):
      guaranteed in fp32, evidence-not-assert in bf16 (two compiled
      programs may round a near-tied logit differently — the same
      boundary gen_spec documents);
    - a failed Pallas arm records ``gen_kernel_pallas_unavailable``
      (deliberately NOT an ``_error`` key — the kept XLA numbers still
      count as a completed stage) — the stage never zeroes the record
      because the fast path regressed.

    ``DISTLLM_BENCH_KERNEL=0`` skips the stage (default on).
    """
    import jax
    import numpy as np

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.models import mistral
    from distllm_tpu.observability.flight import get_flight_recorder

    prefix = 'gen_kernel_'
    if os.environ.get('DISTLLM_BENCH_KERNEL', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_KERNEL=0'}
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        # head_dim is pinned to 128 (not hidden//heads = 32): the Mosaic
        # kernel rejects head_dim % 128 != 0, so without it the fast arm
        # could never run under DISTLLM_BENCH_SMALL on a TPU — and the
        # CPU interpret arm then smokes the exact TPU-eligible geometry.
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, head_dim=128, intermediate_size=512,
            dtype='bfloat16',
        )
        max_num_seqs, num_blocks = 4, 160
        n_prompts, prompt_lo, prompt_hi = 10, 8, 48
        out_lo, out_hi = 4, 24
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        max_num_seqs, num_blocks = 32, 712
        n_prompts, prompt_lo, prompt_hi = 64, 32, 192
        out_lo, out_hi = 16, 96

    # The fast arm: the real Mosaic kernel on TPU, the same kernel under
    # the Pallas interpreter on the CPU smoke (numerics + plumbing, no
    # perf claim — interpret lowers to plain XLA ops).
    fast_backend = 'interpret' if jax.default_backend() == 'cpu' else 'pallas'

    rng = np.random.default_rng(0)
    shared = list(rng.integers(1, model_cfg.vocab_size, size=32))
    prompts = []
    for i, n in enumerate(rng.integers(prompt_lo, prompt_hi, size=n_prompts)):
        tail = list(rng.integers(1, model_cfg.vocab_size, size=int(n)))
        prompts.append(shared + tail if i % 3 == 0 else tail)
    budgets = [int(n) for n in rng.integers(out_lo, out_hi, size=n_prompts)]

    def run_arm(backend: str) -> dict:
        engine_cfg = EngineConfig(
            block_size=16,
            num_blocks=num_blocks,
            max_num_seqs=max_num_seqs,
            max_model_len=512,
            decode_steps=16,
            pipeline_depth=2,
            sampling_top_window=64,
            enable_prefix_cache=True,
            prefill_chunk_tokens=256,
            attn_backend=backend,
        )

        class _Tok:
            eos_id = None

        from distllm_tpu.generate.engine.engine import LLMEngine

        engine = LLMEngine(
            model_cfg,
            mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
            _Tok(), engine_cfg, own_params=True,
        )
        try:
            engine.warmup()
            flight_before = len(get_flight_recorder().snapshot())
            roofline_before = engine.roofline_snapshot()
            rids = [
                engine.add_request(
                    p, SamplingParams(temperature=0.0, max_tokens=n)
                )
                for p, n in zip(prompts, budgets)
            ]
            start = time.perf_counter()
            seen: dict = {rid: [] for rid in rids}
            while engine.has_unfinished:
                for rid, tok in engine.step():
                    seen[rid].append(tok)
            elapsed = time.perf_counter() - start
            n_tokens = sum(len(v) for v in seen.values())
            # Per-window measured truth (compiled.cost_analysis() over
            # wall time; decode/spec fixed-shape dispatches only — see
            # engine._record_step) and the analytic roofline summary for
            # the measured interval.
            records = get_flight_recorder().snapshot()[flight_before:]
            measured_mfu = [
                r['mfu_measured'] for r in records if 'mfu_measured' in r
            ]
            measured_bw = [
                r['bw_util_measured']
                for r in records
                if 'bw_util_measured' in r
            ]
            roofline = engine.roofline_summary(baseline=roofline_before)
            decode_roofline = roofline.get('decode', {})
            arm = {
                'tokens': [seen[rid] for rid in rids],
                'tok_s': round(n_tokens / elapsed, 2),
                'resolved_backend': engine.telemetry['attn_backend'],
                'mfu_measured': (
                    round(float(np.mean(measured_mfu)), 5)
                    if measured_mfu else None
                ),
                'bw_util_measured': (
                    round(float(np.mean(measured_bw)), 5)
                    if measured_bw else None
                ),
                'mfu': decode_roofline.get('mfu'),
                'bw_util': decode_roofline.get('bw_util'),
            }
            return arm
        finally:
            engine.shutdown()

    cache_before = _cache_entries()
    t0 = time.perf_counter()
    xla = run_arm('xla')
    try:
        fast = run_arm(fast_backend)
        fast_error = None
    except Exception as exc:
        fast, fast_error = None, f'{fast_backend}: {exc!r}'[:400]
    elapsed_both = time.perf_counter() - t0

    out = {
        f'{prefix}metric': 'attention-kernel A/B',
        f'{prefix}backend': fast_backend,
        f'{prefix}xla_resolved_backend': xla['resolved_backend'],
        f'{prefix}xla_tok_s': xla['tok_s'],
        f'{prefix}xla_mfu_measured': xla['mfu_measured'],
        f'{prefix}xla_bw_util_measured': xla['bw_util_measured'],
        f'{prefix}xla_mfu': xla['mfu'],
        f'{prefix}xla_bw_util': xla['bw_util'],
        f'{prefix}elapsed_both_arms_s': round(elapsed_both, 1),
        f'{prefix}workload': _workload_fingerprint(
            {'prompts': [list(map(int, p)) for p in prompts],
             'budgets': budgets,
             'engine': {'max_num_seqs': max_num_seqs,
                        'num_blocks': num_blocks,
                        'prefill_chunk_tokens': 256}}
        ),
        **_cache_fields(prefix, cache_before),
    }
    if fast is not None:
        out.update({
            f'{prefix}pallas_tok_s': fast['tok_s'],
            f'{prefix}pallas_mfu_measured': fast['mfu_measured'],
            f'{prefix}pallas_bw_util_measured': fast['bw_util_measured'],
            f'{prefix}pallas_mfu': fast['mfu'],
            f'{prefix}pallas_bw_util': fast['bw_util'],
            f'{prefix}speedup': round(
                fast['tok_s'] / max(xla['tok_s'], 1e-9), 3
            ),
            f'{prefix}tokens_identical': fast['tokens'] == xla['tokens'],
            f'{prefix}resolved_backend': fast['resolved_backend'],
        })
        if fast['tokens'] != xla['tokens']:
            # bf16 near-tie rounding across two compiled programs is the
            # expected cause (fp32 identity is the test-tier assert,
            # tests/test_ragged_attention.py); still worth surfacing.
            out[f'{prefix}identity_note'] = (
                'token streams differ across kernels: expected only from '
                'bf16 near-tie rounding (fp32 identity is asserted in the '
                'fast test tier); investigate if widespread'
            )
    else:
        # NOT an '_error'-suffixed key: per the stage contract the XLA
        # numbers above still count as a completed stage
        # (_completed_stages excludes any fragment carrying *_error /
        # *_skipped keys), and a broken fast arm must truncate the A/B —
        # never zero the round's kernel record.
        out[f'{prefix}pallas_unavailable'] = fast_error
    return out


def _stage_gen_load() -> dict:
    """Open-loop load-generation stage (docs/observability.md): a
    deterministic seeded Poisson arrival stream with a warm/cold prefix
    mix, driven through ``distllm_tpu.generate.loadgen`` against a
    prefix-cached engine with serving-path attribution ON.

    The contract this stage checks and records:

    - TTFT / TPOT / queue-wait p50/p95/p99 (``Histogram.quantile``
      estimates over the request-lifecycle histogram deltas), goodput
      under the configured TTFT SLO, and per-window throughput
      percentiles;
    - per-window-kind MFU and weight-stream bandwidth utilization from
      the engine's roofline accumulators (``roofline_summary``);
    - at least one warm-prefix cache hit (the warm sessions share
      block-aligned prefixes — zero hits means the mix is broken);
    - the SAME workload replayed with attribution flipped OFF emits
      BIT-IDENTICAL tokens (attribution is pure host-side bookkeeping;
      a mismatch is an error in the fragment).

    ``DISTLLM_BENCH_LOAD=0`` skips the stage (chip runs that want the
    deadline for the heavier stages).
    """
    import jax

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.generate.loadgen import (
        LoadgenConfig,
        build_workload,
        run_loadgen,
    )
    from distllm_tpu.models import mistral

    prefix = 'gen_load_'
    if os.environ.get('DISTLLM_BENCH_LOAD', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_LOAD=0'}
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        # fp32, as in gen_tier and gen_router: the identity check then
        # holds whichever prefill kernel a request's timing hands it.
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='float32',
        )
        # max_model_len 128 keeps the CPU-smoke compile ladder at four
        # prefill buckets — warmup dominates this stage's fast-tier cost.
        max_num_seqs, num_blocks, max_model_len, decode_steps = 4, 160, 128, 4
        load_cfg = LoadgenConfig(
            seed=0, num_requests=24, rate_rps=12.0, num_sessions=3,
            warm_fraction=0.6, prefix_tokens=32, prompt_tokens=(8, 40),
            output_tokens=(4, 16), vocab_size=model_cfg.vocab_size,
        )
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        max_num_seqs, num_blocks, max_model_len, decode_steps = (
            32, 712, 512, 16
        )
        load_cfg = LoadgenConfig(
            seed=0, num_requests=256, rate_rps=16.0, num_sessions=16,
            warm_fraction=0.6, prefix_tokens=64, prompt_tokens=(32, 192),
            output_tokens=(16, 96), vocab_size=model_cfg.vocab_size,
        )
    engine_cfg = EngineConfig(
        block_size=16,
        num_blocks=num_blocks,
        max_num_seqs=max_num_seqs,
        max_model_len=max_model_len,
        decode_steps=decode_steps,
        pipeline_depth=2,
        sampling_top_window=64,
        enable_prefix_cache=True,
        ttft_slo_s=2.0,
        attribution=True,
    )
    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    engine = _build_engine(
        model_cfg,
        engine_cfg,
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
        [[1, 2, 3]],
        SamplingParams(temperature=0.0, max_tokens=2),
    )
    warmup_secs = time.perf_counter() - warmup_start

    workload = build_workload(load_cfg)
    on = run_loadgen(engine, workload)
    # Attribution must be pure host-side bookkeeping: the SAME workload
    # replayed on the SAME engine with attribution OFF must emit the same
    # tokens. Both runs start on an empty prefix cache: a prompt served
    # from cached blocks takes the paged tail prefill and the same prompt
    # on a cold cache the dense one, two kernels whose bf16 outputs differ
    # in the last bits (engine._admit says so), enough to flip a greedy
    # near-tie. With no request live every cached block is evictable.
    engine._evict_cached_blocks(engine_cfg.num_blocks)
    engine.attribution = False
    off = run_loadgen(engine, workload)
    engine.attribution = True
    identical = on.tokens_by_request == off.tokens_by_request

    out = {
        f'{prefix}metric': 'open-loop load generation',
        **on.to_fragment(prefix),
        f'{prefix}tokens_identical': identical,
        f'{prefix}attribution_off_tok_s': round(off.achieved_tok_s, 2),
        f'{prefix}slo_s': engine_cfg.ttft_slo_s,
        f'{prefix}attn_backend': engine.telemetry['attn_backend'],
        f'{prefix}warmup_secs': round(warmup_secs, 1),
        f'{prefix}device': str(jax.devices()[0].device_kind),
        f'{prefix}workload': _workload_fingerprint(
            {
                'arrivals': [
                    [a.at_s, list(a.prompt_ids), a.max_tokens, a.session]
                    for a in workload
                ],
                'engine': {'max_num_seqs': max_num_seqs,
                           'num_blocks': num_blocks,
                           'decode_steps': decode_steps},
            }
        ),
        **_cache_fields(prefix, cache_before),
    }
    if not identical:
        out[f'{prefix}error'] = (
            'attribution on/off token mismatch — attribution must be '
            'pure host-side bookkeeping'
        )
    elif on.warm_prefix_hit_tokens <= 0:
        out[f'{prefix}error'] = (
            'no warm-prefix cache hits — the warm/cold session mix is '
            'not exercising the prefix cache'
        )
    return out


def _stage_gen_tier() -> dict:
    """Host-RAM KV tier stage (docs/prefix_caching.md "Tier hierarchy"):
    the loadgen's warm-session workload driven at a paged pool sized
    BELOW the warm working set, so HBM-tier eviction is constant and the
    warm prefixes only survive by spilling to the host tier.

    Two arms over the identical workload:

    - **tier on** (``host_kv_tier_bytes`` generous): evicted prefix
      blocks spill device→host and promote back on re-arrival — records
      warm-session TTFT, spill/promotion counts, and promotion overlap
      efficiency (1 - blocking wait / promotion span);
    - **tier off**: eviction drops KV, every warm repeat whose prefix
      was evicted pays full prefill — the cold TTFT baseline.

    The contract checked into the fragment: warm TTFT (tier on)
    measurably below the tier-off cold TTFT, ≥1 recorded spill and ≥1
    promotion, and tier on/off BIT-IDENTICAL tokens (greedy fp32 in the
    smoke tier — promotion round-trips KV byte-exactly).
    ``DISTLLM_BENCH_TIER=0`` skips the stage.
    """
    import jax

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.generate.loadgen import (
        LoadgenConfig,
        build_workload,
        run_loadgen,
    )
    from distllm_tpu.models import mistral

    prefix = 'gen_tier_'
    if os.environ.get('DISTLLM_BENCH_TIER', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_TIER=0'}
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        # fp32 so the tier on/off identity check is bit-exact across the
        # two separately compiled arms (the acceptance contract); tiny
        # dims keep the two warmups in the fast tier.
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='float32',
        )
        # 47 usable blocks vs a warm working set of 6 sessions x 9
        # prefix blocks (54) + per-request tails + 3 running rows x ~11
        # blocks: session prefixes cannot all stay resident, so warm
        # re-arrivals must spill AND promote, by construction. The
        # 144-token prefix keeps the promotion-vs-reprefill margin
        # visible even at CPU-smoke model dims (a promoted block moves
        # ~linear bytes; re-prefilling it pays the padded 256-bucket
        # dense dispatch).
        max_num_seqs, num_blocks, max_model_len, decode_steps = 3, 48, 256, 4
        load_cfg = LoadgenConfig(
            seed=0, num_requests=32, rate_rps=12.0, num_sessions=6,
            warm_fraction=0.75, prefix_tokens=144, prompt_tokens=(8, 16),
            output_tokens=(4, 10), vocab_size=model_cfg.vocab_size,
            cache_blocks=num_blocks,
        )
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        # Pool ~1/2 the warm working set (16 sessions x 8 prefix blocks
        # + 32 rows x ~24 blocks): chip-scale tier churn.
        max_num_seqs, num_blocks, max_model_len, decode_steps = (
            32, 640, 512, 16
        )
        load_cfg = LoadgenConfig(
            seed=0, num_requests=192, rate_rps=16.0, num_sessions=16,
            warm_fraction=0.65, prefix_tokens=128, prompt_tokens=(32, 160),
            output_tokens=(16, 64), vocab_size=model_cfg.vocab_size,
            cache_blocks=num_blocks,
        )
    workload = build_workload(load_cfg)
    # Warm repeats: warm-session arrivals AFTER the session's first
    # request — the requests whose TTFT the tier exists to shrink.
    seen_sessions: set = set()
    warm_repeat_idx: list[int] = []
    for i, arrival in enumerate(workload):
        if arrival.session is None:
            continue
        if arrival.session in seen_sessions:
            warm_repeat_idx.append(i)
        seen_sessions.add(arrival.session)

    cache_before = _cache_entries()
    warmup_total = 0.0
    reports = {}
    tier: dict = {}
    for arm, tier_bytes in (('on', 256 << 20), ('off', 0)):
        engine_cfg = EngineConfig(
            block_size=16,
            num_blocks=load_cfg.cache_blocks or num_blocks,
            max_num_seqs=max_num_seqs,
            max_model_len=max_model_len,
            decode_steps=decode_steps,
            pipeline_depth=2,
            sampling_top_window=64,
            enable_prefix_cache=True,
            host_kv_tier_bytes=tier_bytes,
            attribution=True,
        )
        warmup_start = time.perf_counter()
        engine = _build_engine(
            model_cfg,
            engine_cfg,
            lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
            [[1, 2, 3]],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        warmup_total += time.perf_counter() - warmup_start
        try:
            reports[arm] = run_loadgen(engine, workload)
            if arm == 'on':
                tier = engine.tier_summary()
        finally:
            # Each arm's weights + KV pool leave the chip before the next
            # arm builds — two resident 7B engines would OOM HBM.
            engine.shutdown()

    on, off = reports['on'], reports['off']
    identical = on.tokens_by_request == off.tokens_by_request

    def _mean_ttft(report) -> float | None:
        vals = [
            report.ttft_by_request[i]
            for i in warm_repeat_idx
            if i < len(report.ttft_by_request)
            and report.ttft_by_request[i] is not None
        ]
        return sum(vals) / len(vals) if vals else None

    warm_ttft = _mean_ttft(on)
    cold_ttft = _mean_ttft(off)
    prompt_tokens = sum(len(a.prompt_ids) for a in workload)
    out = {
        f'{prefix}metric': 'warm-TTFT at cache sizes >> HBM (KV tier)',
        f'{prefix}warm_ttft_s': round(warm_ttft, 6) if warm_ttft else None,
        f'{prefix}cold_ttft_s': round(cold_ttft, 6) if cold_ttft else None,
        f'{prefix}warm_ttft_speedup': (
            round(cold_ttft / warm_ttft, 3)
            if warm_ttft and cold_ttft else None
        ),
        f'{prefix}warm_repeats': len(warm_repeat_idx),
        f'{prefix}tok_s': round(on.achieved_tok_s, 2),
        f'{prefix}tier_off_tok_s': round(off.achieved_tok_s, 2),
        f'{prefix}spills': tier.get('spills'),
        f'{prefix}spilled_blocks': tier.get('spilled_blocks'),
        f'{prefix}promotions': tier.get('promotions'),
        f'{prefix}promoted_blocks': tier.get('promoted_blocks'),
        f'{prefix}promotion_overlap': tier.get('promotion_overlap'),
        f'{prefix}host_blocks': tier.get('host_blocks'),
        f'{prefix}host_bytes': tier.get('host_bytes'),
        f'{prefix}hit_rate': (
            round(on.warm_prefix_hit_tokens / prompt_tokens, 4)
            if prompt_tokens else None
        ),
        f'{prefix}tokens_identical': identical,
        f'{prefix}pool_blocks': load_cfg.cache_blocks or num_blocks,
        f'{prefix}warmup_secs': round(warmup_total, 1),
        f'{prefix}device': str(jax.devices()[0].device_kind),
        f'{prefix}workload': _workload_fingerprint(
            {
                'arrivals': [
                    [a.at_s, list(a.prompt_ids), a.max_tokens, a.session]
                    for a in workload
                ],
                'engine': {'max_num_seqs': max_num_seqs,
                           'num_blocks': num_blocks,
                           'decode_steps': decode_steps},
            }
        ),
        **_cache_fields(prefix, cache_before),
    }
    if not identical:
        out[f'{prefix}error'] = (
            'tier on/off token mismatch — spill→promote round-trips must '
            'be bit-exact against never-evicted KV'
        )
    elif not tier.get('spills') or not tier.get('promotions'):
        out[f'{prefix}error'] = (
            'no spill/promotion recorded — the pool is not below the '
            'warm working set, the tier never engaged'
        )
    elif warm_ttft is None or cold_ttft is None or warm_ttft >= cold_ttft:
        out[f'{prefix}error'] = (
            f'warm TTFT {warm_ttft} not below tier-off cold TTFT '
            f'{cold_ttft} — promotion is not beating re-prefill'
        )
    return out


def _stage_gen_router() -> dict:
    """Multi-replica router stage (docs/routing.md): in-process chat_server
    replicas behind the prefix-affinity router, proven against a
    round-robin control plus a replica-kill failover arm and a direct
    peer-KV-handoff arm.

    Replicas always run at smoke-scale model dims — N engines share ONE
    process and ONE accelerator here, so the stage measures the routing
    and tier deltas (which are dimension-independent), never model FLOPs;
    the non-small tier only widens the workload.

    Four arms:

    - **round_robin** (control): every warm session's prefix lands on
      alternating replicas, so each replica re-prefills (and, with the
      pool below the union working set, churns) prefixes a peer already
      holds;
    - **prefix_affinity**: the router learns residency from the
      ``X-Distllm-Prefix-Digest`` response headers and pins each session
      to one replica — warm-repeat TTFT must beat the control
      (``router_warm_ttft_speedup > 1.0``);
    - **failover**: one of three replicas is killed mid-run with health
      probes effectively off — discovery happens on the proxy path, the
      caught request retries ONCE on a healthy peer (``retried >= 1``),
      goodput stays > 0, zero quarantines, and every survivor answer is
      token-identical to the control arm's answer for the same arrival
      (greedy fp32, same weights: content depends only on the prompt);
    - **peer handoff** (no HTTP): engine A spills a warm prefix to its
      host tier and serves it over the fabric
      (``peer_kv_serve_endpoint``); engine B, cold but configured with
      ``peer_kv_endpoints``, adopts A's blocks like a disk promotion
      (``>= 1`` peer fetch) and must emit tokens bit-identical to a
      peer-less control engine C.

    Per-replica flight rings from the affinity arm are dumped and merged
    into one Perfetto trace (``aggregate.write_combined_perfetto`` — the
    replica-id process naming this PR adds). ``DISTLLM_BENCH_ROUTER=0``
    skips the stage.
    """
    prefix = 'gen_router_'
    if os.environ.get('DISTLLM_BENCH_ROUTER', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_ROUTER=0'}

    import socket
    import threading
    import zlib

    import jax
    import requests
    from aiohttp import web

    from distllm_tpu.chat import ChatAppConfig
    from distllm_tpu.chat_server import build_app
    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.generate.loadgen import (
        LoadgenConfig,
        build_workload,
        run_http_loadgen,
    )
    from distllm_tpu.models import mistral
    from distllm_tpu.observability import instruments
    from distllm_tpu.observability.aggregate import write_combined_perfetto
    from distllm_tpu.observability.flight import FlightRecorder
    from distllm_tpu.observability.metrics import quantile_from_cumulative
    from distllm_tpu.router import RouterConfig, build_router_app

    # N replicas in one process: one metric-history sampler per app would
    # stack 5+ background threads for nothing this stage reads.
    os.environ['DISTLLM_HISTORY_S'] = '0'

    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    # fp32 everywhere: the failover and peer arms gate on token IDENTITY
    # across separately built engines.
    model_cfg = mistral.MistralConfig(
        vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
        num_kv_heads=4, intermediate_size=512, dtype='float32',
    )
    max_num_seqs, num_blocks, max_model_len, decode_steps = 3, 48, 256, 4
    # Pool arithmetic mirrors gen_tier: 6 sessions x 9 shared full prefix
    # blocks (the 'user:'-prefixed 144-id prefix) = 54 > 47 usable, so one
    # replica holding ALL sessions (round-robin) churns; an affinity
    # partition of ~3 sessions/replica (27 blocks) stays resident. The
    # arrival rate is low enough that responses (and therefore learned
    # digests) land before most warm repeats fire — affinity needs the
    # headers to have come back.
    load_cfg = LoadgenConfig(
        seed=0,
        num_requests=32 if small else 96,
        rate_rps=2.0 if small else 4.0,
        num_sessions=6, warm_fraction=0.75, prefix_tokens=144,
        prompt_tokens=(8, 16), output_tokens=(4, 10),
        vocab_size=model_cfg.vocab_size,
    )
    workload = build_workload(load_cfg)
    seen_sessions: set = set()
    warm_repeat_idx: list[int] = []
    for i, arrival in enumerate(workload):
        if arrival.session is None:
            continue
        if arrival.session in seen_sessions:
            warm_repeat_idx.append(i)
        seen_sessions.add(arrival.session)
    last_at = max(a.at_s for a in workload)

    class _EngineChatGenerator:
        """Replica backend: deterministic word-hash tokenizer over the
        rendered chat prompt + greedy engine decode. Exposes ``.engine``
        for the ``/loadinfo`` probe. Two replicas with the same weights
        answer any prompt identically — the failover identity gate."""

        def __init__(self, engine, vocab_size: int, max_tokens: int = 8):
            self.engine = engine
            self.vocab_size = vocab_size
            self.max_tokens = max_tokens

        def _ids(self, prompt: str) -> list[int]:
            ids = []
            for word in prompt.split():
                if word.isdigit():
                    ids.append(int(word) % (self.vocab_size - 2) + 1)
                else:
                    ids.append(
                        zlib.crc32(word.encode()) % (self.vocab_size - 1) + 1
                    )
            return ids

        def generate(self, prompts: list[str]) -> list[str]:
            outs = self.engine.generate_ids(
                [self._ids(p) for p in prompts],
                SamplingParams(temperature=0.0,
                               max_tokens=self.max_tokens),
            )
            return [' '.join(str(t) for t in out) for out in outs]

    from typing import ClassVar

    class _ReplicaChatConfig(ChatAppConfig):
        """ChatAppConfig whose generator is a pre-built in-process engine
        wrapper (keyed off-model: pydantic configs must stay YAML-shaped,
        a live engine is not a field)."""

        replica_key: int = 0
        _live_generators: ClassVar[dict] = {}

        def build_generator(self):
            return type(self)._live_generators[self.replica_key]

    def _serve_app(app) -> tuple[str, 'callable']:
        """Boot an aiohttp app on a free port in a daemon thread; returns
        ``(base_url, idempotent_stop)`` (tests/test_chat.py pattern)."""
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            port = s.getsockname()[1]
        holder: dict = {}

        def run():
            import asyncio

            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            holder['loop'] = loop
            # Short shutdown grace: the failover arm kills a replica
            # mid-run and needs the port gone NOW, not in 60 s.
            runner = web.AppRunner(app, shutdown_timeout=1.0)
            loop.run_until_complete(runner.setup())
            site = web.TCPSite(runner, '127.0.0.1', port)
            loop.run_until_complete(site.start())
            holder['runner'] = runner
            loop.run_forever()

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        for _ in range(100):
            try:
                requests.get(f'http://127.0.0.1:{port}/health', timeout=1)
                break
            except Exception:
                time.sleep(0.05)

        done = {'stopped': False}

        def stop():
            if done['stopped']:
                return
            done['stopped'] = True
            loop = holder['loop']

            async def _shutdown():
                await holder['runner'].cleanup()
                loop.stop()

            loop.call_soon_threadsafe(
                lambda: loop.create_task(_shutdown())
            )
            thread.join(timeout=10)

        return f'http://127.0.0.1:{port}', stop

    replica_counter = {'next': 0}

    def _build_replica(engine_cfg: EngineConfig):
        """One replica: fresh engine (+ its own flight ring) behind its
        own chat_server app. Returns (engine, url, stop)."""
        engine = _build_engine(
            model_cfg,
            engine_cfg,
            lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
            [[1, 2, 3]],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        engine.flight = FlightRecorder()
        key = replica_counter['next']
        replica_counter['next'] += 1
        _ReplicaChatConfig._live_generators[key] = _EngineChatGenerator(
            engine, model_cfg.vocab_size
        )
        url, stop = _serve_app(
            build_app(_ReplicaChatConfig(replica_key=key))
        )
        return engine, url, stop

    def _replica_engine_cfg() -> EngineConfig:
        return EngineConfig(
            block_size=16, num_blocks=num_blocks,
            max_num_seqs=max_num_seqs, max_model_len=max_model_len,
            decode_steps=decode_steps, pipeline_depth=2,
            sampling_top_window=64, enable_prefix_cache=True,
            attribution=True,
        )

    def _counter_total(counter) -> float:
        return sum(child.value for _, child in counter.children())

    bundle = _bundle_dir('gen_router')
    os.makedirs(bundle, exist_ok=True)
    cache_before = _cache_entries()
    warmup_total = 0.0
    arm_stats: dict[str, dict] = {}
    flight_paths: list[str] = []

    def _run_router_arm(
        arm: str, policy: str, n_replicas: int, kill_idx: int | None = None
    ) -> dict:
        nonlocal warmup_total
        engines, stops, urls = [], [], []
        warmup_start = time.perf_counter()
        try:
            for _ in range(n_replicas):
                engine, url, stop = _build_replica(
                    _replica_engine_cfg()
                )
                engines.append(engine)
                urls.append(url)
                stops.append(stop)
            warmup_total += time.perf_counter() - warmup_start
            router_cfg = RouterConfig(
                replicas=tuple(urls),
                policy=policy,
                loadinfo_ttl_s=0.05,
                # Failover: probes effectively off, so the kill is
                # DISCOVERED on the proxy path (the retry contract),
                # not masked by a lucky health tick.
                health_interval_s=30.0 if kill_idx is not None else 0.5,
                request_timeout_s=60.0,
            )
            router_url, router_stop = _serve_app(
                build_router_app(router_cfg)
            )
            stops.append(router_stop)
            decisions_before = {
                k: child.value
                for k, child in instruments.ROUTER_REQUESTS.children()
            }
            counters_before = {
                'retries': instruments.ROUTER_RETRIES.value,
                'quarantined': _counter_total(
                    instruments.RESILIENCE_QUARANTINED
                ),
            }
            tpot_before = instruments.REQUEST_TPOT.cumulative_counts()
            killer = None
            if kill_idx is not None:
                killer = threading.Timer(
                    max(0.5, 0.35 * last_at), stops[kill_idx]
                )
                killer.start()
            try:
                report = run_http_loadgen(
                    router_url, workload, slo_s=0.0, timeout_s=60.0
                )
            finally:
                if killer is not None:
                    killer.cancel()
            tpot_delta = [
                after - before
                for after, before in zip(
                    instruments.REQUEST_TPOT.cumulative_counts(),
                    tpot_before,
                )
            ]
            warm_ttfts = [
                report.ttft_by_request[i]
                for i in warm_repeat_idx
                if i < len(report.ttft_by_request)
                and report.ttft_by_request[i] is not None
                and report.statuses[i] == 200
            ]
            if arm == 'prefix_affinity':
                for r, engine in enumerate(engines):
                    path = os.path.join(bundle, f'replica-{r}')
                    os.makedirs(path, exist_ok=True)
                    path = os.path.join(path, 'flight.jsonl')
                    engine.flight.dump_jsonl(path)
                    flight_paths.append(path)
            return {
                'report': report,
                'warm_ttft': (
                    sum(warm_ttfts) / len(warm_ttfts)
                    if warm_ttfts else None
                ),
                'tpot': {
                    f'p{q}': round(
                        quantile_from_cumulative(
                            instruments.REQUEST_TPOT.buckets,
                            tpot_delta, q / 100.0,
                        ) or 0.0, 6,
                    )
                    for q in (50, 95, 99)
                } if tpot_delta and tpot_delta[-1] > 0 else {},
                'decisions': {
                    '/'.join(k): round(
                        child.value - decisions_before.get(k, 0.0)
                    )
                    for k, child in
                    instruments.ROUTER_REQUESTS.children()
                    if child.value > decisions_before.get(k, 0.0)
                },
                'retries_delta': (
                    instruments.ROUTER_RETRIES.value
                    - counters_before['retries']
                ),
                'quarantined_delta': (
                    _counter_total(instruments.RESILIENCE_QUARANTINED)
                    - counters_before['quarantined']
                ),
            }
        finally:
            for stop in stops:
                stop()
            for engine in engines:
                engine.shutdown()

    arm_stats['round_robin'] = _run_router_arm('round_robin',
                                               'round_robin', 2)
    arm_stats['prefix_affinity'] = _run_router_arm('prefix_affinity',
                                                   'prefix_affinity', 2)
    arm_stats['failover'] = _run_router_arm('failover', 'round_robin', 3,
                                            kill_idx=0)

    # ------------------------------------------------ peer handoff arm
    # Direct engines, no HTTP: A spills a warm prefix to its host tier
    # and serves it over the fabric; B adopts it as a peer promotion; C
    # is the cold control the tokens must match bit-for-bit.
    peer_prompt = [1 + (i * 7) % (model_cfg.vocab_size - 8)
                   for i in range(150)]
    junk_prompts = [
        [2 + (j * 997 + i * 13) % (model_cfg.vocab_size - 8)
         for i in range(150)]
        for j in range(6)
    ]
    peer_params = SamplingParams(temperature=0.0, max_tokens=8)
    peer_hits_before = instruments.PREFIX_TIER_HITS.labels(
        tier='peer'
    ).value

    def _peer_engine_cfg(**overrides) -> EngineConfig:
        cfg = _replica_engine_cfg().model_copy(
            update={'host_kv_tier_bytes': 64 << 20, **overrides}
        )
        return cfg

    warmup_start = time.perf_counter()
    engine_a = _build_engine(
        model_cfg,
        _peer_engine_cfg(peer_kv_serve_endpoint='tcp://127.0.0.1:0'),
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
        [[1, 2, 3]],
        SamplingParams(temperature=0.0, max_tokens=2),
    )
    peer_summary: dict = {}
    try:
        engine_a.generate_ids([peer_prompt], peer_params)
        for junk in junk_prompts:
            engine_a.generate_ids([junk], peer_params)
        spills_a = engine_a.tier_summary().get('spills', 0)

        engine_b = _build_engine(
            model_cfg,
            _peer_engine_cfg(
                peer_kv_endpoints=(engine_a.peer_kv_endpoint,)
            ),
            lambda: mistral.init_on_device(
                jax.random.PRNGKey(0), model_cfg
            ),
            [[1, 2, 3]],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        try:
            tokens_b = engine_b.generate_ids([peer_prompt], peer_params)
            peer_summary = {
                **engine_b.tier_summary(),
                'spills_a': spills_a,
                'served_blocks_a': engine_a.tier_summary().get(
                    'peer_served_blocks', 0
                ),
            }
        finally:
            engine_b.shutdown()
    finally:
        engine_a.shutdown()

    engine_c = _build_engine(
        model_cfg,
        _peer_engine_cfg(),
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
        [[1, 2, 3]],
        SamplingParams(temperature=0.0, max_tokens=2),
    )
    try:
        tokens_c = engine_c.generate_ids([peer_prompt], peer_params)
    finally:
        engine_c.shutdown()
    warmup_total += time.perf_counter() - warmup_start
    peer_hits = (
        instruments.PREFIX_TIER_HITS.labels(tier='peer').value
        - peer_hits_before
    )
    peer_identical = tokens_b == tokens_c

    # -------------------------------------------------- merged Perfetto
    perfetto_path = os.path.join(bundle, 'combined_perfetto.json')
    perfetto_inputs = write_combined_perfetto(flight_paths, perfetto_path)

    rr, aff, failover = (
        arm_stats['round_robin'],
        arm_stats['prefix_affinity'],
        arm_stats['failover'],
    )
    speedup = (
        round(rr['warm_ttft'] / aff['warm_ttft'], 3)
        if rr['warm_ttft'] and aff['warm_ttft'] else None
    )
    # Survivor identity: every failover 200 must carry the SAME content
    # the control arm produced for that arrival — greedy fp32 engines
    # built from one PRNG key answer by prompt alone, so a kill must not
    # perturb a single survivor token.
    survivor_identical = all(
        content == rr['report'].contents[i]
        for i, content in enumerate(failover['report'].contents)
        if failover['report'].statuses[i] == 200
        and rr['report'].statuses[i] == 200
    )

    out = {
        f'{prefix}metric': (
            'warm-repeat TTFT, prefix-affinity routing vs round-robin '
            '(2 replicas)'
        ),
        f'{prefix}router_warm_ttft_speedup': speedup,
        f'{prefix}affinity_warm_ttft_s': (
            round(aff['warm_ttft'], 6) if aff['warm_ttft'] else None
        ),
        f'{prefix}rr_warm_ttft_s': (
            round(rr['warm_ttft'], 6) if rr['warm_ttft'] else None
        ),
        f'{prefix}warm_repeats': len(warm_repeat_idx),
        f'{prefix}failover_goodput': round(
            failover['report'].goodput_rps, 3
        ),
        f'{prefix}failover_retried': failover['report'].retried,
        f'{prefix}failover_router_retries': round(
            failover['retries_delta']
        ),
        f'{prefix}failover_errors': failover['report'].errors,
        f'{prefix}failover_quarantines': round(
            failover['quarantined_delta']
        ),
        f'{prefix}failover_survivor_tokens_identical': survivor_identical,
        f'{prefix}peer_hits': round(peer_hits),
        f'{prefix}peer_fetched_blocks': peer_summary.get(
            'peer_fetched_blocks'
        ),
        f'{prefix}peer_fetched_bytes': peer_summary.get(
            'peer_fetched_bytes'
        ),
        f'{prefix}peer_served_blocks': peer_summary.get(
            'served_blocks_a'
        ),
        f'{prefix}peer_spills': peer_summary.get('spills_a'),
        f'{prefix}peer_tokens_identical': peer_identical,
        f'{prefix}perfetto_inputs': perfetto_inputs,
        f'{prefix}perfetto_path': perfetto_path,
        f'{prefix}workload': _workload_fingerprint(
            {
                'arrivals': [
                    [a.at_s, list(a.prompt_ids), a.max_tokens, a.session]
                    for a in workload
                ],
                'engine': {'max_num_seqs': max_num_seqs,
                           'num_blocks': num_blocks,
                           'decode_steps': decode_steps},
            }
        ),
        f'{prefix}warmup_secs': round(warmup_total, 1),
        f'{prefix}device': str(jax.devices()[0].device_kind),
        **_cache_fields(prefix, cache_before),
    }
    for arm in ('round_robin', 'prefix_affinity', 'failover'):
        stats = arm_stats[arm]
        report = stats['report']
        tag = {'round_robin': 'rr', 'prefix_affinity': 'affinity',
               'failover': 'failover'}[arm]
        out[f'{prefix}{tag}_ok'] = report.ok
        out[f'{prefix}{tag}_goodput_rps'] = round(report.goodput_rps, 3)
        out[f'{prefix}{tag}_decisions'] = stats['decisions']
        for key, value in report.percentiles.items():
            out[f'{prefix}{tag}_{key}'] = (
                round(value, 6) if value is not None else None
            )
        for key, value in stats['tpot'].items():
            out[f'{prefix}{tag}_tpot_{key}'] = value

    if speedup is None or speedup <= 1.0:
        out[f'{prefix}error'] = (
            f'affinity warm TTFT speedup {speedup} not > 1.0 over '
            'round-robin — digest learning is not concentrating sessions'
        )
    elif failover['report'].retried < 1 or (
        failover['report'].goodput_rps <= 0
    ):
        out[f'{prefix}error'] = (
            f'failover arm retried={failover["report"].retried} '
            f'goodput={failover["report"].goodput_rps} — the kill was '
            'not absorbed by the retry-once contract'
        )
    elif failover['quarantined_delta'] or not survivor_identical:
        out[f'{prefix}error'] = (
            'failover perturbed the survivors '
            f'(quarantines={failover["quarantined_delta"]}, '
            f'identical={survivor_identical}) — a dead peer must cost '
            'its own in-flight requests at most'
        )
    elif peer_hits < 1 or not peer_summary.get('peer_fetched_blocks'):
        out[f'{prefix}error'] = (
            'no peer-tier hit recorded — the spilled prefix never '
            'crossed the fabric (check spills_a and the tier walk)'
        )
    elif not peer_identical:
        out[f'{prefix}error'] = (
            'peer-adopted tokens differ from the cold control — the '
            '.kvblock payload did not round-trip byte-exactly'
        )
    return out


def _stage_gen_chaos() -> dict:
    """Chaos serving stage (docs/resilience.md): the open-loop Poisson
    loadgen driven through a DETERMINISTIC fault schedule, gating that the
    resilience layer actually survives what it claims to.

    Three arms on one engine:

    - **clean** (cold cache): the fault-free baseline token streams;
    - **chaos** (same workload, faults armed): dispatch raises, a window
      stall, and an injected scheduler exhaustion fire on a fixed call
      schedule while the loadgen keeps offering load — records
      goodput-under-fault, recovery count, retries, and quarantines;
    - **overload** (denser schedule, admission control ON with a tight
      SLO): shed rate + Retry-After behavior, informational by design
      (shed volume is offered-load policy, not quality).

    The contract checked into the fragment: every armed fault fired,
    ≥1 recovery, zero quarantines (the schedule is survivable by
    construction), nonzero goodput while faults were firing, and chaos
    tokens BIT-IDENTICAL to the clean arm (greedy fp32 in the smoke
    tier — recovery must replay, not approximate).
    ``DISTLLM_BENCH_CHAOS=0`` skips the stage.
    """
    import jax

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.generate.loadgen import (
        LoadgenConfig,
        build_workload,
        run_loadgen,
    )
    from distllm_tpu.models import mistral
    from distllm_tpu.resilience import get_fault_injector

    prefix = 'gen_chaos_'
    if os.environ.get('DISTLLM_BENCH_CHAOS', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_CHAOS=0'}
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        # fp32 so the chaos/clean identity check is bit-exact (recovery
        # re-dispatches must replay the same stream); tiny dims keep the
        # single warmup in the fast tier.
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='float32',
        )
        max_num_seqs, num_blocks, max_model_len, decode_steps = 4, 160, 128, 4
        load_cfg = LoadgenConfig(
            seed=0, num_requests=24, rate_rps=16.0, num_sessions=3,
            warm_fraction=0.5, prefix_tokens=32, prompt_tokens=(8, 32),
            output_tokens=(4, 12), vocab_size=model_cfg.vocab_size,
        )
        overload_cfg = LoadgenConfig(
            seed=1, num_requests=32, rate_rps=200.0, num_sessions=3,
            warm_fraction=0.5, prefix_tokens=32, prompt_tokens=(8, 32),
            output_tokens=(4, 12), vocab_size=model_cfg.vocab_size,
        )
        slo_s, overload_slo_s, deadline_s = 2.0, 0.02, 60.0
        fault_schedule = (
            ('dispatch', dict(times=2, after=4)),
            ('slow_window', dict(times=2, delay_s=0.02, after=2)),
            ('sched_exhausted', dict(times=1, after=10)),
        )
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        max_num_seqs, num_blocks, max_model_len, decode_steps = (
            32, 712, 512, 16
        )
        load_cfg = LoadgenConfig(
            seed=0, num_requests=192, rate_rps=16.0, num_sessions=16,
            warm_fraction=0.6, prefix_tokens=64, prompt_tokens=(32, 192),
            output_tokens=(16, 96), vocab_size=model_cfg.vocab_size,
        )
        overload_cfg = LoadgenConfig(
            seed=1, num_requests=128, rate_rps=256.0, num_sessions=16,
            warm_fraction=0.6, prefix_tokens=64, prompt_tokens=(32, 192),
            output_tokens=(16, 64), vocab_size=model_cfg.vocab_size,
        )
        slo_s, overload_slo_s, deadline_s = 4.0, 0.25, 120.0
        fault_schedule = (
            ('dispatch', dict(times=3, after=16)),
            ('slow_window', dict(times=3, delay_s=0.2, after=8)),
            ('sched_exhausted', dict(times=2, after=32)),
        )
    engine_cfg = EngineConfig(
        block_size=16,
        num_blocks=num_blocks,
        max_num_seqs=max_num_seqs,
        max_model_len=max_model_len,
        decode_steps=decode_steps,
        pipeline_depth=2,
        sampling_top_window=64,
        enable_prefix_cache=True,
        ttft_slo_s=slo_s,
        request_deadline_s=deadline_s,
        max_dispatch_retries=3,
        retry_backoff_s=0.01,
        attribution=True,
    )
    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    engine = _build_engine(
        model_cfg,
        engine_cfg,
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
        [[1, 2, 3]],
        SamplingParams(temperature=0.0, max_tokens=2),
    )
    warmup_secs = time.perf_counter() - warmup_start

    workload = build_workload(load_cfg)
    clean = run_loadgen(engine, workload)

    injector = get_fault_injector()
    faults_by_site: dict[str, int] = {}
    try:
        for site, kwargs in fault_schedule:
            injector.arm(site, **kwargs)
        chaos = run_loadgen(engine, workload)
        faults_by_site = {
            site: injector.fired(site) for site, _ in fault_schedule
        }
    finally:
        injector.disarm()

    # Overload arm: admission control on, SLO tightened to the point the
    # denser schedule must shed — the 429/Retry-After surface exercised
    # end-to-end, reported informationally (shed volume is policy).
    engine.config.ttft_slo_s = overload_slo_s
    engine.admission_control = True
    overload = run_loadgen(engine, build_workload(overload_cfg))
    engine.admission_control = False
    engine.config.ttft_slo_s = slo_s

    identical = chaos.tokens_by_request == clean.tokens_by_request
    faults_injected = sum(faults_by_site.values())
    out = {
        f'{prefix}metric': 'goodput + recovery under an injected fault '
                           'schedule',
        f'{prefix}tok_s': round(chaos.achieved_tok_s, 2),
        f'{prefix}clean_tok_s': round(clean.achieved_tok_s, 2),
        f'{prefix}goodput_tokens': chaos.goodput_tokens,
        f'{prefix}goodput_frac': chaos.goodput_frac,
        f'{prefix}recoveries': chaos.recoveries,
        f'{prefix}retries': chaos.window_retries,
        f'{prefix}quarantined': chaos.quarantined,
        f'{prefix}failed_requests': chaos.failed_requests,
        f'{prefix}faults_injected': faults_injected,
        **{
            f'{prefix}faults_{site}': count
            for site, count in faults_by_site.items()
        },
        f'{prefix}tokens_identical': identical,
        f'{prefix}shed_requests': overload.shed_requests,
        f'{prefix}shed_rate': overload.shed_rate,
        f'{prefix}overload_slo_met': overload.slo_met,
        f'{prefix}overload_slo_missed': overload.slo_missed,
        f'{prefix}slo_s': slo_s,
        f'{prefix}deadline_s': deadline_s,
        f'{prefix}warmup_secs': round(warmup_secs, 1),
        f'{prefix}device': str(jax.devices()[0].device_kind),
        f'{prefix}workload': _workload_fingerprint(
            {
                'arrivals': [
                    [a.at_s, list(a.prompt_ids), a.max_tokens, a.session]
                    for a in workload
                ],
                'faults': [
                    [site, sorted(kwargs.items())]
                    for site, kwargs in fault_schedule
                ],
                'engine': {'max_num_seqs': max_num_seqs,
                           'num_blocks': num_blocks,
                           'decode_steps': decode_steps},
            }
        ),
        **_cache_fields(prefix, cache_before),
    }
    if any(count == 0 for count in faults_by_site.values()):
        # Per-site, not total: 4 dispatch fires must not paper over a
        # sched_exhausted schedule that never engaged its hazard point.
        out[f'{prefix}error'] = (
            f'armed fault site(s) never fired: {faults_by_site} — the '
            'schedule did not engage every hazard point it targets'
        )
    elif not identical:
        out[f'{prefix}error'] = (
            'chaos/clean token mismatch — recovery must replay the '
            'fault-free stream bit-exactly (greedy fp32), not '
            'approximate it'
        )
    elif chaos.recoveries < 1:
        out[f'{prefix}error'] = (
            'faults fired but no recovery was recorded — the retry '
            'ladder never engaged'
        )
    elif chaos.quarantined or chaos.failed_requests:
        out[f'{prefix}error'] = (
            f'{chaos.quarantined} quarantined / {chaos.failed_requests} '
            'failed requests on a survivable fault schedule'
        )
    elif not chaos.goodput_tokens:
        out[f'{prefix}error'] = (
            'zero goodput under fault — the engine stopped serving '
            'while faults were firing'
        )
    return out


def _stage_gen_history() -> dict:
    """Telemetry-history serving stage (docs/observability.md "Metric
    history & sampling"): the open-loop loadgen with the metric-history
    ring live, gating that the retention layer, the SLO burn-rate
    engine, and the runtime regression sentinel actually work against
    real traffic — not just unit fixtures.

    Five arms on one engine:

    - **clean** (sampler on): fault-free serving; its measured tok/s and
      TTFT/TPOT p95 distill into a baseline envelope through the SHARED
      ``build_envelope`` (the ``benchdiff.py --emit-baseline`` code
      path, so this stage and the offline gate can never disagree on
      what a record says);
    - **identity** (sampler OFF): the same workload with no sampler
      thread running — history is pure host-side observation, so tokens
      must be BIT-IDENTICAL to the clean arm (greedy fp32; asserted,
      not assumed);
    - **verify** (sentinel armed with the clean envelope): the same
      workload again — a sentinel judging a run statistically identical
      to its own baseline must stay QUIET (0 regressions);
    - **slow** (``slow_window`` fault armed): every decode window eats an
      injected sleep, throughput collapses — the sentinel must fire
      ≥ 1 regression, and a second pass must fire 0 (the episode latch);
    - **overload** (admission control + a hopeless TTFT SLO, denser
      schedule): misses flow into ``distllm_request_slo_total`` and the
      60 s burn-rate gauge must move off zero.

    Thread hygiene rides along: after the stage stops its sampler, no
    live thread may carry ``SAMPLER_THREAD_NAME``.
    ``DISTLLM_BENCH_HISTORY=0`` skips the stage.
    """
    import threading

    import jax

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.generate.loadgen import (
        LoadgenConfig,
        build_workload,
        run_loadgen,
    )
    from distllm_tpu.models import mistral
    from distllm_tpu.observability.baseline import build_envelope
    from distllm_tpu.observability.history import (
        SAMPLER_THREAD_NAME,
        HistorySampler,
        get_metrics_history,
    )
    from distllm_tpu.observability.sentinel import RegressionSentinel
    from distllm_tpu.observability.slo import slo_status, update_burn_gauges
    from distllm_tpu.resilience import get_fault_injector

    prefix = 'gen_history_'
    if os.environ.get('DISTLLM_BENCH_HISTORY', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_HISTORY=0'}
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        # fp32 so the history-on/off identity check is bit-exact; tiny
        # dims keep the single warmup in the fast tier.
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='float32',
        )
        max_num_seqs, num_blocks, max_model_len, decode_steps = 4, 160, 128, 4
        load_cfg = LoadgenConfig(
            seed=0, num_requests=24, rate_rps=16.0, num_sessions=3,
            warm_fraction=0.5, prefix_tokens=32, prompt_tokens=(8, 32),
            output_tokens=(4, 12), vocab_size=model_cfg.vocab_size,
        )
        overload_cfg = LoadgenConfig(
            seed=1, num_requests=32, rate_rps=200.0, num_sessions=3,
            warm_fraction=0.5, prefix_tokens=32, prompt_tokens=(8, 32),
            output_tokens=(4, 12), vocab_size=model_cfg.vocab_size,
        )
        slo_s, overload_slo_s = 2.0, 0.02
        sample_interval_s, slow_delay_s = 0.25, 0.2
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        max_num_seqs, num_blocks, max_model_len, decode_steps = (
            32, 712, 512, 16
        )
        load_cfg = LoadgenConfig(
            seed=0, num_requests=192, rate_rps=16.0, num_sessions=16,
            warm_fraction=0.6, prefix_tokens=64, prompt_tokens=(32, 192),
            output_tokens=(16, 96), vocab_size=model_cfg.vocab_size,
        )
        overload_cfg = LoadgenConfig(
            seed=1, num_requests=128, rate_rps=256.0, num_sessions=16,
            warm_fraction=0.6, prefix_tokens=64, prompt_tokens=(32, 192),
            output_tokens=(16, 64), vocab_size=model_cfg.vocab_size,
        )
        slo_s, overload_slo_s = 4.0, 0.25
        sample_interval_s, slow_delay_s = 1.0, 0.5
    engine_cfg = EngineConfig(
        block_size=16,
        num_blocks=num_blocks,
        max_num_seqs=max_num_seqs,
        max_model_len=max_model_len,
        decode_steps=decode_steps,
        pipeline_depth=2,
        sampling_top_window=64,
        enable_prefix_cache=True,
        ttft_slo_s=slo_s,
        attribution=True,
    )
    cache_before = _cache_entries()
    warmup_start = time.perf_counter()
    engine = _build_engine(
        model_cfg,
        engine_cfg,
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
        [[1, 2, 3]],
        SamplingParams(temperature=0.0, max_tokens=2),
    )
    warmup_secs = time.perf_counter() - warmup_start

    history = get_metrics_history()
    history.clear()  # this stage's windows, not a prior stage's tail
    sampler = HistorySampler(history, interval_s=sample_interval_s)
    workload = build_workload(load_cfg)

    # Clean arm (sampler on) → the live-measured baseline envelope.
    sampler.start()
    clean = run_loadgen(engine, workload)
    history.sample_once()  # fold the tail before the envelope reads
    envelope = build_envelope(
        {
            f'{prefix}tok_s': clean.achieved_tok_s,
            f'{prefix}ttft_p95': clean.percentiles.get('ttft_p95'),
            f'{prefix}tpot_p95': clean.percentiles.get('tpot_p95'),
        },
        source='gen_history clean arm',
    )

    # Identity arm: sampler stopped — history off must not change tokens.
    sampler.stop()
    identity = run_loadgen(engine, workload)
    identical = identity.tokens_by_request == clean.tokens_by_request
    sampler.start()

    # Verify arm: the sentinel armed with the clean arm's own envelope
    # must stay quiet on a statistically identical run. Thresholds are
    # loose (50%) because live windows include idle sampler ticks the
    # end-of-run aggregate never sees.
    verify = run_loadgen(engine, workload)
    history.sample_once()
    sentinel_quiet = RegressionSentinel(
        history, envelope=envelope, threshold=0.5,
        window_s=verify.elapsed_s + 2.0 * sample_interval_s,
    )
    clean_fired = sentinel_quiet.evaluate()

    # Slow arm: a per-window injected sleep collapses throughput; the
    # sentinel must notice, and its episode latch must fire only once.
    injector = get_fault_injector()
    try:
        injector.arm(
            'slow_window', times=10**6, delay_s=slow_delay_s, after=0
        )
        slow = run_loadgen(engine, workload)
    finally:
        injector.disarm()
    history.sample_once()
    sentinel_slow = RegressionSentinel(
        history, envelope=envelope, threshold=0.5,
        window_s=slow.elapsed_s + 2.0 * sample_interval_s,
    )
    slow_fired = sentinel_slow.evaluate()
    slow_refired = sentinel_slow.evaluate()  # latched: must be empty

    # Overload arm (a): a hopeless TTFT SLO with admission OFF — every
    # arrival is served and judged, so the misses flow into
    # ``distllm_request_slo_total`` and the 60 s burn gauge must move.
    engine.config.ttft_slo_s = overload_slo_s
    overload = run_loadgen(engine, build_workload(overload_cfg))
    history.sample_once()
    burns = update_burn_gauges(history)
    verdict = slo_status(history)['verdict']
    # Overload arm (b): the same schedule with admission control ON —
    # the shed path under the same pressure (informational, like
    # gen_chaos: shed volume is offered-load policy, not quality).
    engine.admission_control = True
    shed_run = run_loadgen(engine, build_workload(overload_cfg))
    engine.admission_control = False
    engine.config.ttft_slo_s = slo_s

    sampler.stop()
    leaked = any(
        t.name == SAMPLER_THREAD_NAME for t in threading.enumerate()
    )

    out = {
        f'{prefix}metric': 'live history + sentinel + burn rates under '
                           'real traffic',
        f'{prefix}tok_s': round(clean.achieved_tok_s, 2),
        f'{prefix}ttft_p95': clean.percentiles.get('ttft_p95'),
        f'{prefix}tpot_p95': clean.percentiles.get('tpot_p95'),
        f'{prefix}goodput_tokens': clean.goodput_tokens,
        f'{prefix}samples': history.samples,
        f'{prefix}envelope_metrics': len(envelope['metrics']),
        f'{prefix}tokens_identical': identical,
        f'{prefix}clean_regressions': len(clean_fired),
        f'{prefix}slow_regressions': len(slow_fired),
        f'{prefix}slow_relatch_regressions': len(slow_refired),
        f'{prefix}slow_tok_s': round(slow.achieved_tok_s, 2),
        f'{prefix}slow_fired_metrics': sorted(
            e['metric'] for e in slow_fired
        ),
        f'{prefix}burn_60s': round(burns['60s'], 3),
        f'{prefix}slo_verdict': verdict,
        f'{prefix}overload_slo_missed': overload.slo_missed,
        f'{prefix}shed_requests': shed_run.shed_requests,
        f'{prefix}sampler_leaked': leaked,
        f'{prefix}warmup_secs': round(warmup_secs, 1),
        f'{prefix}device': str(jax.devices()[0].device_kind),
        f'{prefix}workload': _workload_fingerprint(
            {
                'arrivals': [
                    [a.at_s, list(a.prompt_ids), a.max_tokens, a.session]
                    for a in workload
                ],
                'engine': {'max_num_seqs': max_num_seqs,
                           'num_blocks': num_blocks,
                           'decode_steps': decode_steps},
                'slow_delay_s': slow_delay_s,
            }
        ),
        **_cache_fields(prefix, cache_before),
    }
    if not envelope['metrics']:
        out[f'{prefix}error'] = (
            'clean arm produced an empty baseline envelope — the shared '
            'extraction found none of its own stage keys'
        )
    elif not identical:
        out[f'{prefix}error'] = (
            'history on/off token mismatch — sampling must be pure '
            'observation (greedy fp32), it may never perturb serving'
        )
    elif clean_fired:
        out[f'{prefix}error'] = (
            f'sentinel fired {len(clean_fired)} regression(s) on a run '
            'statistically identical to its own baseline: '
            f'{[e["metric"] for e in clean_fired]}'
        )
    elif not slow_fired:
        out[f'{prefix}error'] = (
            'slow_window fault collapsed throughput '
            f'({clean.achieved_tok_s:.1f} -> {slow.achieved_tok_s:.1f} '
            'tok/s) but the sentinel never fired'
        )
    elif slow_refired:
        out[f'{prefix}error'] = (
            'sentinel re-fired on a latched degradation episode — '
            'once-per-episode alarm discipline is broken'
        )
    elif not overload.slo_missed:
        out[f'{prefix}error'] = (
            'overload arm recorded zero SLO misses — the burn-rate '
            'check below would be vacuous'
        )
    elif burns['60s'] <= 0:
        out[f'{prefix}error'] = (
            f'{overload.slo_missed} SLO misses but the 60s burn-rate '
            'gauge never moved off zero'
        )
    elif leaked:
        out[f'{prefix}error'] = (
            'a sampler thread is still alive after stop() — the '
            'shutdown contract leaks threads'
        )
    return out


def _stage_gen_kvq() -> dict:
    """Quantized-KV-cache A/B (docs/serving.md "Quantized KV cache"): the
    SAME staggered greedy workload (the gen_mixed shape — shared-prefix
    repeats, staggered finish budgets) through a bf16-KV arm and an
    int8-KV arm of ``EngineConfig.kv_cache_dtype``, same model weights,
    same pool geometry.

    The contract this stage checks and records:

    - tok/s per arm (``gen_kvq_bf16_tok_s`` / ``gen_kvq_int8_tok_s``)
      and their ratio (``gen_kvq_speedup``);
    - MEASURED bandwidth utilization per arm (mean of the per-window
      ``bw_util_measured`` flight fields — ``compiled.cost_analysis()``
      truth, docs/observability.md) plus each arm's measured
      per-decode-dispatch bytes (``*_decode_bytes_accessed``) and exact
      KV pool bytes (``*_kv_pool_bytes``): the int8 pool is ~half the
      bf16 pool and the measured dispatch bytes must drop by the KV
      share — roofline EVIDENCE, not a modelled claim;
    - admission capacity at fixed pool bytes
      (``gen_kvq_int8_capacity_blocks``): how many int8 blocks — data
      plus their per-block scales — the bf16 arm's HBM budget would
      hold, i.e. the extra sequences the same chip admits;
    - the ACCURACY arm: ``gen_kvq_greedy_match``, the fraction of int8
      greedy tokens matching the bf16 stream position-for-position over
      the paired requests. Divergence is RECORDED, never asserted away;
      scripts/benchdiff.py gates the fraction higher-better (the
      'greedy_match' token), so a lossier compression trips the
      trajectory gate exactly like a throughput fall.

    A failed int8 arm records ``gen_kvq_error`` — unlike gen_kernel's
    fast arm, the quantized pool is the stage's whole subject, so its
    absence IS a stage failure. ``DISTLLM_BENCH_KVQ=0`` skips (default
    on).
    """
    import jax
    import numpy as np

    from distllm_tpu.generate.engine.engine import EngineConfig, SamplingParams
    from distllm_tpu.models import mistral
    from distllm_tpu.observability.flight import get_flight_recorder

    prefix = 'gen_kvq_'
    if os.environ.get('DISTLLM_BENCH_KVQ', '1') in ('', '0'):
        return {f'{prefix}skipped': 'DISTLLM_BENCH_KVQ=0'}
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
        max_num_seqs, num_blocks = 4, 80
        n_prompts, prompt_lo, prompt_hi = 10, 8, 48
        out_lo, out_hi = 4, 24
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')  # 7B defaults
        max_num_seqs, num_blocks = 32, 356
        n_prompts, prompt_lo, prompt_hi = 64, 32, 192
        out_lo, out_hi = 16, 96

    rng = np.random.default_rng(0)
    # The gen_mixed staggered shape: every third prompt repeats a shared
    # prefix (RAG/MCQA), finish budgets stagger so slots free mid-stream
    # and decode windows carry mixed work — the serving regime where KV
    # bandwidth, not weights, is the decode bottleneck.
    shared = list(rng.integers(1, model_cfg.vocab_size, size=32))
    prompts = []
    for i, n in enumerate(rng.integers(prompt_lo, prompt_hi, size=n_prompts)):
        tail = list(rng.integers(1, model_cfg.vocab_size, size=int(n)))
        prompts.append(shared + tail if i % 3 == 0 else tail)
    budgets = [int(n) for n in rng.integers(out_lo, out_hi, size=n_prompts)]

    def run_arm(kv_dtype: str) -> dict:
        # block_size=32 (not the gen-stage-usual 16): the int8 sublane
        # tile (ops.paged_attention.kv_sublane_tile) — BOTH arms use it
        # so the A/B compares KV dtype, never pool geometry, and the
        # int8 arm stays Pallas-eligible on TPU.
        engine_cfg = EngineConfig(
            block_size=32,
            num_blocks=num_blocks,
            max_num_seqs=max_num_seqs,
            max_model_len=512,
            decode_steps=16,
            pipeline_depth=2,
            sampling_top_window=64,
            enable_prefix_cache=True,
            prefill_chunk_tokens=256,
            kv_cache_dtype=kv_dtype,
        )
        engine = _build_engine(
            model_cfg,
            engine_cfg,
            lambda: mistral.init_on_device(jax.random.PRNGKey(0), model_cfg),
            [[1, 2, 3]],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        try:
            flight_before = len(get_flight_recorder().snapshot())
            rids = [
                engine.add_request(
                    p, SamplingParams(temperature=0.0, max_tokens=n)
                )
                for p, n in zip(prompts, budgets)
            ]
            start = time.perf_counter()
            seen: dict = {rid: [] for rid in rids}
            while engine.has_unfinished:
                for rid, tok in engine.step():
                    seen[rid].append(tok)
            elapsed = time.perf_counter() - start
            n_tokens = sum(len(v) for v in seen.values())
            records = get_flight_recorder().snapshot()[flight_before:]
            measured_bw = [
                r['bw_util_measured']
                for r in records
                if 'bw_util_measured' in r
            ]
            decode_cost = engine.measured_costs().get('decode', {})
            return {
                'tokens': [seen[rid] for rid in rids],
                'tok_s': round(n_tokens / elapsed, 2),
                'resolved_backend': engine.telemetry['attn_backend'],
                'kv_cache_dtype': engine.telemetry['kv_cache_dtype'],
                'bw_util_measured': (
                    round(float(np.mean(measured_bw)), 5)
                    if measured_bw else None
                ),
                'decode_bytes_accessed': decode_cost.get('bytes_accessed'),
                'kv_pool_bytes': int(engine.kv.hbm_bytes),
            }
        finally:
            engine.shutdown()

    cache_before = _cache_entries()
    t0 = time.perf_counter()
    bf16 = run_arm('bf16')
    try:
        q8 = run_arm('int8')
        q8_error = None
    except Exception as exc:
        q8, q8_error = None, f'int8 arm: {exc!r}'[:400]
    elapsed_both = time.perf_counter() - t0

    out = {
        f'{prefix}metric': 'bf16-KV vs int8-KV A/B',
        f'{prefix}bf16_tok_s': bf16['tok_s'],
        f'{prefix}bf16_bw_util_measured': bf16['bw_util_measured'],
        f'{prefix}bf16_decode_bytes_accessed': bf16['decode_bytes_accessed'],
        f'{prefix}bf16_kv_pool_bytes': bf16['kv_pool_bytes'],
        f'{prefix}bf16_resolved_backend': bf16['resolved_backend'],
        f'{prefix}elapsed_both_arms_s': round(elapsed_both, 1),
        f'{prefix}workload': _workload_fingerprint(
            {'prompts': [list(map(int, p)) for p in prompts],
             'budgets': budgets,
             'engine': {'max_num_seqs': max_num_seqs,
                        'num_blocks': num_blocks,
                        'block_size': 32,
                        'prefill_chunk_tokens': 256}}
        ),
        **_cache_fields(prefix, cache_before),
    }
    if q8 is not None:
        # The accuracy arm: position-for-position greedy agreement over
        # the paired streams. Divergent-length tails count as misses
        # (max, not min, in the denominator) — an early-stopping stream
        # is itself a divergence, not a shorter exam.
        matched = total = 0
        for a, b in zip(bf16['tokens'], q8['tokens']):
            total += max(len(a), len(b))
            matched += sum(1 for x, y in zip(a, b) if x == y)
        # Admission capacity at FIXED pool bytes: the block count the
        # bf16 arm's HBM budget funds when each block is int8 data plus
        # its fp32 per-(block, KV-head) scales.
        per_block_q8 = q8['kv_pool_bytes'] / num_blocks
        out.update({
            f'{prefix}int8_tok_s': q8['tok_s'],
            f'{prefix}int8_bw_util_measured': q8['bw_util_measured'],
            f'{prefix}int8_decode_bytes_accessed': (
                q8['decode_bytes_accessed']
            ),
            f'{prefix}int8_kv_pool_bytes': q8['kv_pool_bytes'],
            f'{prefix}int8_resolved_backend': q8['resolved_backend'],
            f'{prefix}int8_kv_cache_dtype': q8['kv_cache_dtype'],
            f'{prefix}kv_pool_bytes_ratio': round(
                q8['kv_pool_bytes'] / max(bf16['kv_pool_bytes'], 1), 4
            ),
            f'{prefix}bf16_capacity_blocks': num_blocks,
            f'{prefix}int8_capacity_blocks': int(
                bf16['kv_pool_bytes'] // per_block_q8
            ),
            f'{prefix}speedup': round(
                q8['tok_s'] / max(bf16['tok_s'], 1e-9), 3
            ),
            f'{prefix}greedy_match': round(matched / max(total, 1), 4),
        })
    else:
        out[f'{prefix}error'] = q8_error
    return out


def _stage_gen() -> dict:
    return _run_gen(None, 'gen_')


def _stage_gen_q() -> dict:
    return _run_gen('int8', 'gen_int8_')


def _stage_embed_q() -> dict:
    return _stage_embed('int8', 'embed_int8_')


def _chip_peak_flops(device) -> float | None:
    """bf16 peak FLOP/s from the one peaks table (an unknown TPU kind
    raises there). ``None`` on the CPU test tier: a CPU run writes no MFU."""
    if device.platform == 'cpu':
        return None
    from distllm_tpu.observability.roofline import device_peaks

    return device_peaks(device)[0]


# ------------------------------------------------------------ orchestrator

# Cheapest-first: embed warmups are minutes, gen_prefix reuses gen's
# compile cache (same bf16 7B dims), and int8 gen_q's cold warmup — the
# round-4 22-45 min outlier — runs last so a deadline truncates the most
# expensive coverage first, never the headline metrics.
STAGE_ORDER = (
    'embed', 'embed_q', 'gen', 'gen_prefix', 'gen_mixed', 'gen_spec',
    'gen_kernel', 'gen_load', 'gen_tier', 'gen_router', 'gen_chaos',
    'gen_history', 'gen_kvq', 'gen_q',
)
NOMINAL_BUDGET_S = {
    'embed': 1200.0,
    'embed_q': 1200.0,
    'gen': 2700.0,
    'gen_prefix': 2700.0,
    'gen_mixed': 2700.0,
    'gen_spec': 2700.0,
    'gen_kernel': 2700.0,
    'gen_load': 2700.0,
    'gen_tier': 2700.0,
    'gen_router': 2700.0,
    'gen_chaos': 2700.0,
    'gen_history': 2700.0,
    'gen_kvq': 2700.0,
    'gen_q': 2700.0,
}
GEN_STAGES = frozenset(
    {'gen', 'gen_q', 'gen_prefix', 'gen_mixed', 'gen_spec', 'gen_kernel',
     'gen_load', 'gen_tier', 'gen_router', 'gen_chaos', 'gen_history',
     'gen_kvq'}
)
# Under a 1 h driver timeout (rc 124 in r5 was `timeout` sending SIGTERM):
# stages stop with ~5 min to spare even if the guess is exact, and the
# SIGTERM handler is the backstop if the real budget is shorter.
DEFAULT_DEADLINE_S = 3300.0

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))
# Orchestrator state shared with the signal handlers.
_CURRENT_CHILD: dict = {'proc': None}
_EMITTED = {'done': False}


def _record_paths() -> tuple[str, str]:
    base = os.environ.get('DISTLLM_BENCH_RECORD_DIR') or _REPO_DIR
    return (
        os.path.join(base, 'BENCH_partial.jsonl'),
        os.path.join(base, 'BENCH_snapshot.json'),
    )


def _bundle_dir(stage: str) -> str:
    base = os.environ.get('DISTLLM_BENCH_BUNDLE_DIR') or os.path.join(
        _REPO_DIR, 'bench_debug'
    )
    return os.path.join(base, f'{stage}_{os.getpid()}')


def _completed_stages(record) -> list[str]:
    """Stages whose recorded fragment carries metrics, not an error/skip."""
    done: list[str] = []
    for entry in record.entries():
        stage = entry.get('stage')
        fragment = entry.get('fragment') or {}
        if (
            stage in NOMINAL_BUDGET_S
            and stage not in done
            and not any(
                key.endswith(('_error', '_skipped')) for key in fragment
            )
        ):
            done.append(stage)
    return done


def _run_failed(record) -> bool:
    """Did the probe fail or any stage end in ``*_error``?"""
    return any(
        entry.get('stage') == 'probe_failed'
        or any(key.endswith('_error') for key in entry.get('fragment') or {})
        for entry in record.entries()
    )


def _emit_final(record, base: dict, extra: dict) -> None:
    """Compose + print the single driver-contract line, exactly once.

    Called from normal exit AND from the SIGTERM/SIGALRM handlers. Must be
    async-signal-tolerant: it reads the on-disk record (no locks shared
    with the main thread) and writes stdout directly.
    """
    if _EMITTED['done']:
        return
    _EMITTED['done'] = True
    result = dict(base)
    result.update(record.compose())
    result.update(extra)
    result['stages_completed'] = _completed_stages(record)
    sys.stdout.write(json.dumps(result) + '\n')
    sys.stdout.flush()


def _probe_backend(record) -> str | None:
    """Confirm the backend comes up, in one short child that has exited
    before the first stage starts. One attempt: unless the caller asked
    for ``JAX_PLATFORMS=cpu`` the platform must be ``tpu`` — a bench that
    quietly ran on whatever came up would publish CPU numbers under
    device metric names. Returns None on success, else the error."""
    probe_src = 'import jax\nprint(jax.devices()[0].platform)\n'
    timeout_s = float(os.environ.get('DISTLLM_BENCH_PROBE_TIMEOUT_S', '150'))
    want = 'cpu' if os.environ.get('JAX_PLATFORMS') == 'cpu' else 'tpu'
    start = time.monotonic()
    outcome: dict = {'want_platform': want}
    try:
        proc = subprocess.run(
            [sys.executable, '-c', probe_src],
            capture_output=True, text=True, timeout=timeout_s,
        )
        platform = proc.stdout.strip()[-40:]
        if proc.returncode != 0:
            err = (proc.stderr or '').strip()[-500:]
        elif platform != want:
            err = f'backend came up as {platform!r}, need {want!r}'
        else:
            err = None
        outcome.update(platform=platform)
    except subprocess.TimeoutExpired:
        err = f'backend init timed out after {timeout_s:.0f}s'
    outcome.update(
        outcome='ok' if err is None else 'error',
        elapsed_s=round(time.monotonic() - start, 1),
    )
    if err is not None:
        outcome['error'] = err[-200:]
    record.record('probe', {'probe_attempts': [outcome]})
    return err


def _run_stage(stage: str, timeout: float) -> tuple[dict, str]:
    """Run one stage in a subprocess; parse its single JSON stdout line.

    Returns ``(fragment, outcome)`` with outcome ok/error/timeout. On
    timeout the child gets SIGTERM first (its handler dumps a debug
    bundle — the corpse carries evidence), then SIGKILL after a grace
    period.
    """
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), '--stage', stage],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    _CURRENT_CHILD['proc'] = proc
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.terminate()  # SIGTERM: the stage dumps its bundle and exits
        try:
            out, err = proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
        fragment = {f'{stage}_error': f'stage timed out after {timeout:.0f}s'}
        bundle = _stage_bundle_hint(err)
        if bundle:
            fragment[f'{stage}_bundle_dir'] = bundle
        return fragment, 'timeout'
    finally:
        _CURRENT_CHILD['proc'] = None
    if proc.returncode != 0:
        fragment = {f'{stage}_error': (err or out or '').strip()[-800:]}
        bundle = _stage_bundle_hint(err)
        if bundle:
            fragment[f'{stage}_bundle_dir'] = bundle
        return fragment, 'error'
    for line in reversed((out or '').strip().splitlines()):
        try:
            return json.loads(line), 'ok'
        except json.JSONDecodeError:
            continue
    return (
        {f'{stage}_error': f'no JSON in stage output: {(out or "")[-300:]}'},
        'error',
    )


def _stage_bundle_hint(stderr: str | None) -> str | None:
    """The stage prints ``[bench-bundle] <dir>`` to stderr when it dumps a
    debug bundle; surface that path in the run record."""
    for line in reversed((stderr or '').splitlines()):
        if line.startswith('[bench-bundle] '):
            return line[len('[bench-bundle] '):].strip()
    return None


def _run_stage_entry(stage: str) -> None:
    """``--stage`` subprocess body: run the stage fn, print its fragment.

    Failure paths dump a debug bundle (flight ring + metrics + traces) so
    a dead stage still explains itself: on exception, AND on the SIGTERM
    the orchestrator sends at budget expiry. Gen stages additionally run
    under a StallWatchdog (the engine's flight ring is the progress
    signal) that dumps a bundle if the chip wedges mid-stage.
    """
    from distllm_tpu.observability.flight import (
        StallWatchdog,
        dump_debug_bundle,
    )
    from distllm_tpu.observability.startup import record_backend_init

    # Smoke-test hook (tests/test_smoke_bench_contract.py): park this stage
    # before any heavy import so the orchestrator's kill paths can be
    # exercised in seconds.
    if os.environ.get('DISTLLM_BENCH_TEST_HANG_STAGE') == stage:
        while True:
            time.sleep(1)

    bundle_dir = _bundle_dir(stage)

    def _dump(reason: str) -> None:
        try:
            dump_debug_bundle(bundle_dir, reason=reason)
            print(f'[bench-bundle] {bundle_dir}', file=sys.stderr, flush=True)
        except Exception:
            pass

    # Attribute this stage subprocess's REAL backend init: by the time an
    # engine exists the PJRT client is already up (params load first), so
    # the engine-side record measures ~0 — here is where r03/r04's wedged
    # init actually happened. A dead backend raises AFTER the phase
    # records the error, so the bundle carries it.
    try:
        record_backend_init()
    except Exception as exc:
        _dump(f'{stage}: backend init failed: {exc!r}'[:300])
        raise

    def _on_sigterm(signum, frame):  # budget kill from the orchestrator
        _dump(f'{stage}: SIGTERM (stage budget expired)')
        os._exit(143)

    signal.signal(signal.SIGTERM, _on_sigterm)

    stage_fns = {
        'embed': _stage_embed,
        'embed_q': _stage_embed_q,
        'gen': _stage_gen,
        'gen_q': _stage_gen_q,
        'gen_prefix': _stage_gen_prefix,
        'gen_mixed': _stage_gen_mixed,
        'gen_spec': _stage_gen_spec,
        'gen_kernel': _stage_gen_kernel,
        'gen_load': _stage_gen_load,
        'gen_tier': _stage_gen_tier,
        'gen_router': _stage_gen_router,
        'gen_chaos': _stage_gen_chaos,
        'gen_history': _stage_gen_history,
        'gen_kvq': _stage_gen_kvq,
    }
    watchdog = None
    watchdog_s = float(os.environ.get('DISTLLM_BENCH_WATCHDOG_S', '300') or 0)
    if stage in GEN_STAGES and watchdog_s > 0:
        watchdog = StallWatchdog(
            watchdog_s, bundle_dir=bundle_dir, name=f'bench-{stage}'
        ).start()
    try:
        fragment = stage_fns[stage]()
    except BaseException as exc:
        _dump(f'{stage}: {exc!r}'[:300])
        raise
    finally:
        if watchdog is not None:
            watchdog.stop()
    print(json.dumps(fragment))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        '--stage',
        choices=[
            'embed', 'embed_q', 'gen', 'gen_q', 'gen_prefix', 'gen_mixed',
            'gen_spec', 'gen_kernel', 'gen_load', 'gen_tier', 'gen_router',
            'gen_chaos', 'gen_history', 'gen_kvq',
        ],
    )
    args = parser.parse_args()

    # One process for each chip: only a stage child touches JAX. This
    # parent (below) never imports it — a parent that has touched JAX
    # holds the chip, and the stage children would fail or hang. Tests
    # force the CPU with JAX_PLATFORMS=cpu, which jax reads itself.
    if args.stage:
        from distllm_tpu.utils import enable_compile_cache

        # XLA compiles amortize across runs (the 7B engine has ~25 serving
        # shapes): JAX_COMPILATION_CACHE_DIR when set, else
        # <checkout>/.jax_cache.
        enable_compile_cache()
        _run_stage_entry(args.stage)
        return

    from distllm_tpu.observability.flight import Deadline, RunRecord

    base: dict = {
        'metric': 'embeddings/sec/chip',
        'value': 0.0,
        'unit': 'emb/s',
        'vs_baseline': 0.0,
    }
    # Setup itself can fail (unwritable record dir, non-numeric deadline
    # env, full disk) — before the signal handlers and the emit-protected
    # try/finally exist. Even then the driver must get a parseable line.
    try:
        deadline = Deadline(
            float(
                os.environ.get('DISTLLM_BENCH_DEADLINE_S')
                or DEFAULT_DEADLINE_S
            ),
            reserve_s=20.0,
        )
        partial_path, snapshot_path = _record_paths()
        # Each orchestrator run is a fresh record: a stale partial file
        # from a previous run must not leak its stages into this run's
        # contract line.
        for stale in (partial_path, snapshot_path):
            try:
                os.unlink(stale)
            except OSError:
                pass
        record = RunRecord(partial_path, snapshot_path)
    except BaseException as exc:
        base['error'] = f'bench orchestrator setup failed: {exc!r}'[:500]
        sys.stdout.write(json.dumps(base) + '\n')
        sys.stdout.flush()
        raise

    def _on_signal(signum, frame):
        # Runs in the main thread, possibly mid-communicate(): touch no
        # locks the main thread could hold — read the on-disk record,
        # emit, hard-exit. Exit 0: the line on stdout IS the result.
        reason = (
            'deadline_expired' if signum == signal.SIGALRM else 'sigterm'
        )
        child = _CURRENT_CHILD.get('proc')
        if child is not None:
            try:
                child.terminate()
            except Exception:
                pass
        _emit_final(
            record,
            base,
            {
                'interrupted': reason,
                'deadline_s': deadline.total_s,
                'elapsed_s': round(deadline.elapsed(), 1),
            },
        )
        os._exit(0)

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGALRM, _on_signal)
    # The alarm is the deadline made unconditional: even a wedged
    # communicate() or a hung probe gets interrupted in time to emit.
    signal.alarm(max(1, int(deadline.total_s)))

    # EVERY exit path emits: signals are handled above, and the finally
    # below covers exceptions (a typo'd stage name, a full disk, a broken
    # env override) — an orchestrator bug must not re-open the zeroed-
    # record failure this file exists to close. _emit_final is idempotent.
    try:
        record.record(
            'run',
            {
                'bench_deadline_s': deadline.total_s,
                'bench_started_wall_s': round(time.time(), 1),
            },
        )
        probe_err = _probe_backend(record)
        if probe_err is not None:
            record.record(
                'probe_failed',
                {'error': f'TPU backend unavailable: {probe_err}'},
            )

        stages_env = os.environ.get('DISTLLM_BENCH_STAGES')
        stages = (
            []  # no backend, no stage
            if probe_err is not None
            else [s.strip() for s in stages_env.split(',') if s.strip()]
            if stages_env
            else list(STAGE_ORDER)
        )
        # Budget override for smoke tests: a single float applies to every
        # stage, a JSON object ({"gen": 5}) per stage.
        override = os.environ.get('DISTLLM_BENCH_STAGE_TIMEOUT_S', '').strip()
        overrides: dict = (
            json.loads(override) if override.startswith('{')
            else dict.fromkeys(NOMINAL_BUDGET_S, float(override)) if override
            else {}
        )
        floor_s = float(os.environ.get('DISTLLM_BENCH_STAGE_FLOOR_S', '60'))
        outcomes: dict = {}
        for stage in stages:
            nominal = float(overrides.get(stage, NOMINAL_BUDGET_S[stage]))
            budget = deadline.budget(nominal, floor_s=min(floor_s, nominal))
            if budget <= 0:
                outcomes[stage] = 'skipped'
                record.record(
                    stage,
                    {
                        f'{stage}_skipped': (
                            f'deadline: {deadline.remaining():.0f}s left of '
                            f'{deadline.total_s:.0f}s'
                        ),
                        'bench_stage_outcomes': dict(outcomes),
                    },
                )
                continue
            fragment, outcome = _run_stage(stage, budget)
            outcomes[stage] = outcome
            fragment['bench_stage_outcomes'] = dict(outcomes)
            record.record(stage, fragment)
    except BaseException as exc:
        try:
            record.record(
                'orchestrator_error',
                {'orchestrator_error': repr(exc)[:300]},
            )
        except Exception:
            pass
        raise
    finally:
        _emit_final(record, base, {})
    # The line above is the record; the exit code says whether it is whole.
    sys.exit(1 if _run_failed(record) else 0)


if __name__ == '__main__':
    main()
