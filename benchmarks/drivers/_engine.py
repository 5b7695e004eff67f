"""What the two engine drivers share: building ``LLMEngine`` in process with
weights made on the device from the seed, warming up the cell's own shapes,
and the correctness check against the plain reference.

Only public names of the engine are used (``LLMEngine``, ``EngineConfig``,
``SamplingParams``, ``add_request``, ``step``, ``generate_ids``,
``has_unfinished``, ``telemetry``, ``flight``, ``shutdown``): a later PR may
move a private field and may not edit the benchmark.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from benchmarks import reference, traffic

CHECK_PROMPTS = 4
CHECK_PROMPT_TOKENS = (48, 100)
CHECK_OUTPUT_TOKENS = 8


class _NoTokenizer:
    """The engine asks its tokenizer only for ``eos_id``; the benchmark sends
    token ids, and with no EOS every request runs to its budget."""

    eos_id = None


def _model_cfg(model: dict):
    from distllm_tpu.models import mistral

    return mistral.MistralConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weights(ctx):
    """The program's parameter tree (``mistral.init_on_device``'s shapes and
    types), filled on the device in one jitted call that takes the key as an
    ARGUMENT: normal(0, 0.02) kernels, unit norm scales. The program's own
    ``init_on_device`` closes over the key, so every new seed is a new
    program and a fresh compile of about 25 s (my chip runs, PR 23); this one
    is compiled once and found in the cache by every seed."""
    from distllm_tpu.models import mistral

    cfg = _model_cfg(ctx.config)
    shapes = jax.eval_shape(
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def fill(key):
        leaves = []
        for sub, (path, leaf) in zip(jax.random.split(key, len(paths)), paths):
            if any('ln' in str(getattr(p, 'key', '')) for p in path):
                leaves.append(jax.numpy.ones(leaf.shape, leaf.dtype))
            else:
                normal = jax.random.normal(sub, leaf.shape, jax.numpy.float32)
                leaves.append((normal * 0.02).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return fill(jax.random.PRNGKey(ctx.seed % (2**31)))


def build(ctx) -> dict:
    from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine

    model = ctx.config
    engine = LLMEngine(
        _model_cfg(model), _weights(ctx), _NoTokenizer(),
        EngineConfig(**model['engine'], seed=ctx.seed % (2**31)),
        own_params=True,
    )
    backend = engine.telemetry['attn_backend']
    if not ctx.rehearsal and backend != model['expect_attn_backend']:
        engine.shutdown()
        raise RuntimeError(
            f"attn_backend resolved to {backend!r}, the configuration states "
            f"{model['expect_attn_backend']!r}"
        )
    return {'engine': engine, 'attn_backend': backend}


def sampling(ctx, max_tokens: int):
    from distllm_tpu.generate.engine.engine import SamplingParams

    spec = ctx.workload['sampling']
    return SamplingParams(
        temperature=float(spec['temperature']),
        top_p=float(spec.get('top_p', 1.0)),
        max_tokens=int(max_tokens),
    )


def warmup_calls(ctx) -> list[tuple[list[list[int]], int]]:
    """The warm-up calls, each its prompts and their output budget, from the
    cell's ``warmup``. Every entry names shapes that the cell's traffic can
    make the engine dispatch:

    ``dense`` lists ``[bucket, batch]`` pairs: one call per batch size holds
    ``batch`` fresh prompts of every listed bucket, which the engine prefills
    as one padded dispatch per bucket. ``chunk_tails`` lists lengths of
    prompts that prefill in chunks, one dispatch each. ``paged`` lists
    ``[bucket, batch]`` pairs of the paged route for a short tail behind
    cached blocks, which a request takes when it is preempted and admitted
    again with its prompt's blocks still in the prefix cache: one call per
    pair, ``batch`` prompts that share a cached two-block prefix. Each of
    these calls generates 2 tokens: the prefill's and one decode window.
    ``decode_windows`` adds one call that runs that many windows back to
    back, which the pipelined loop needs to merge carried token ids.
    """
    spec = ctx.workload['warmup']
    rng = traffic.rng_for(ctx.seed, 'warmup')
    vocab = ctx.config['vocab_size']

    def fresh(n: int) -> list[int]:
        return traffic.token_ids(n, vocab, rng)

    calls = []
    if spec.get('paged'):
        prefix = fresh(2 * ctx.config['engine']['block_size'])
        calls.append(([prefix + fresh(100)], 2))  # caches the prefix
        for bucket, batch in spec['paged']:
            tail = max(1, int(bucket) * 3 // 4)
            calls.append(
                ([prefix + fresh(tail) for _ in range(int(batch))], 2)
            )
    if spec.get('chunk_tails'):
        calls.append(([fresh(int(n)) for n in spec['chunk_tails']], 2))
    by_batch: dict[int, list[int]] = {}
    for bucket, batch in spec.get('dense', []):
        by_batch.setdefault(int(batch), []).extend([int(bucket)] * int(batch))
    calls.extend(([fresh(n) for n in by_batch[b]], 2) for b in sorted(by_batch))
    if spec.get('decode_windows'):
        steps = ctx.config['engine']['decode_steps']
        calls.append(
            ([fresh(100) for _ in range(4)],
             1 + int(spec['decode_windows']) * steps)
        )
    return calls


def check_prompts(ctx) -> list[list[int]]:
    rng = traffic.rng_for(ctx.seed, 'check')
    lo, hi = CHECK_PROMPT_TOKENS
    return [
        traffic.token_ids(int(rng.integers(lo, hi + 1)), ctx.config['vocab_size'], rng)
        for _ in range(CHECK_PROMPTS)
    ]


def sample_for_check(state, ctx) -> float:
    """Greedy continuations of the check prompts through the engine, kept for
    ``verify``. Returns the seconds it took (outside set-up and window)."""
    from distllm_tpu.generate.engine.engine import SamplingParams

    t = time.perf_counter()
    prompts = check_prompts(ctx)
    outputs = state['engine'].generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=CHECK_OUTPUT_TOKENS)
    )
    state['check'] = (prompts, outputs)
    return time.perf_counter() - t


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced: the reference scores each check prompt with the
    engine's own tokens appended; every token must lie within
    ``reference.TOKEN_GAP_LIMIT_STD`` of the reference's largest logit."""
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs = state['check']
    lengths_ok = all(len(o) == CHECK_OUTPUT_TOKENS for o in outputs)
    params = _weights(ctx)
    width = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    ids = np.zeros((len(prompts), width), np.int32)
    for row, (p, o) in enumerate(zip(prompts, outputs)):
        ids[row, : len(p) + len(o)] = list(p) + list(o)
    logits = reference.mistral_logits(params, ctx.config, ids)
    gaps = reference.token_gaps(logits, [len(p) for p in prompts], outputs)
    del params, logits
    worst = max(gaps) if gaps else float('inf')
    correct = (
        lengths_ok
        and worst <= reference.TOKEN_GAP_LIMIT_STD
        and obs['failed'] == 0
        and (ctx.rehearsal
             or state['attn_backend'] == ctx.config['expect_attn_backend'])
    )
    return correct, {
        'token_gap_max_std': worst,
        'attn_backend': state['attn_backend'],
    }


def close(state) -> None:
    engine = state.pop('engine', None)
    if engine is not None:
        engine.shutdown()
        del engine
        gc.collect()


def flight_since(engine, recorded_before: int) -> list[dict]:
    """The flight records made since ``engine.flight.total_recorded`` read
    ``recorded_before`` (fewer where the ring, 4096 deep, has wrapped)."""
    grew = engine.flight.total_recorded - recorded_before
    return engine.flight.snapshot()[-grew:] if grew > 0 else []
