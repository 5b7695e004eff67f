"""Closed loop over ``LLMEngine.generate_ids`` for ``laguna`` (poolside
Laguna-XS.2): ``engine_closed``'s loop and window with this architecture's
own model config, seeded weights and plain reference. The cell's file names
this driver; nothing else here knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from benchmarks import reference_laguna
from benchmarks.drivers import _engine, engine_closed

# The check runs at the cell's load: one greedy call of a whole call's
# prompts with the cell's output budget, through the timed path. CHECK_ROWS
# of them, evenly spaced by prompt length from the shortest to the longest
# (so rows inside the window, past it, and past the YaRN original length of
# 4096), are scored by the reference: every token of each.
CHECK_ROWS = 8


def _model_cfg(model: dict):
    from distllm_tpu.models import laguna

    return laguna.LagunaConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import laguna

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(
        lambda: laguna.init_on_device(jax.random.PRNGKey(0), cfg)
    )


def _reference_widths(ctx) -> list[int]:
    """The check's rows are padded on the right (a causal forward never
    sees it) to one of three widths, so that the reference compiles three
    shapes a kind of layer and not one for every prompt length."""
    longest = ctx.config['engine']['max_model_len']
    return [longest // 4, longest // 2, longest]


def _compile_reference_ahead(ctx, split: dict) -> None:
    """On a thread beside the engine's set-up: the reference's programs
    compiled from shapes into the compile cache (``compile_ahead``), where
    ``verify`` finds them. With no cache they are 5-14 s each, after the
    window, inside the run's time limit; the host has cores to spare while
    the main thread builds and warms the engine. A failure here costs
    ``verify`` that time again and nothing else."""
    t = time.perf_counter()
    try:
        reference_laguna.compile_ahead(
            ctx.config, _weight_shapes(ctx), _reference_widths(ctx),
            scored=int(ctx.traffic['output_tokens']['value']),
            kv_rows=2 * ctx.config['engine']['block_size'],
        )
        split['reference_ahead'] = round(time.perf_counter() - t, 1)
    except Exception as exc:  # noqa: BLE001 -- the check compiles them itself
        split['reference_ahead'] = f'failed: {exc!r}'[:200]


def _weights(ctx):
    """The program's parameter tree (``laguna.init_on_device``'s shapes and
    types), filled on the device in one jitted call that takes the key as
    an ARGUMENT, so that every seed finds one compiled program: normal(0,
    0.02) kernels, unit norm scales."""
    shapes = _weight_shapes(ctx)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    jnp = jax.numpy

    @jax.jit
    def fill(key):
        leaves = []
        for sub, (path, leaf) in zip(jax.random.split(key, len(paths)), paths):
            if str(getattr(path[-1], 'key', '')) == 'scale':
                value = jnp.ones(leaf.shape, jnp.float32)
            else:
                value = jax.random.normal(sub, leaf.shape, jnp.float32) * 0.02
            leaves.append(value.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return fill(jax.random.PRNGKey(ctx.seed % (2**31)))


def build(ctx) -> dict:
    from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine

    model = ctx.config
    t0 = time.perf_counter()
    weights = jax.block_until_ready(_weights(ctx))
    t1 = time.perf_counter()
    engine = LLMEngine(
        _model_cfg(model), weights, _engine._NoTokenizer(),
        EngineConfig(**model['engine'], seed=ctx.seed % (2**31)),
        own_params=True,
    )
    del weights
    backend = engine.telemetry['attn_backend']
    if not ctx.rehearsal and backend != model['expect_attn_backend']:
        engine.shutdown()
        raise RuntimeError(
            f"attn_backend resolved to {backend!r}, the configuration states "
            f"{model['expect_attn_backend']!r}"
        )
    return {
        'engine': engine, 'attn_backend': backend,
        'kv_pools': engine.telemetry['kv_pools'],
        'setup_split_s': {
            'weights': round(t1 - t0, 1),
            'engine': round(time.perf_counter() - t1, 1),
        },
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.laguna  # noqa: F401 -- fail first

    ahead_split: dict = {}
    ahead = threading.Thread(
        target=_compile_reference_ahead, args=(ctx, ahead_split), daemon=True
    )
    ahead.start()
    state = build(ctx)
    engine = state['engine']
    budget = int(ctx.traffic['output_tokens']['value'])
    t = time.perf_counter()
    for n in range(int(ctx.workload.get('warmup', {}).get('replica_calls', 1))):
        engine.generate_ids(
            engine_closed._call_prompts(ctx, f'warmup{n}'),
            _engine.sampling(ctx, budget),
        )
    state['setup_split_s']['warmup_calls'] = round(time.perf_counter() - t, 1)
    state['excluded_s'] = sample_for_check(state, ctx)
    ahead.join()  # never beside the window
    state['setup_split_s'].update(_compile_seconds(engine), **ahead_split)
    return state


def _compile_seconds(engine) -> dict:
    """Where set-up's compile time went, from the engine's ``compile``
    flight records: the start-up phases by name, and the programs jax
    compiled or loaded from the compile cache (the check's own among them)."""
    records = [r for r in engine.flight.snapshot() if r['kind'] == 'compile']
    programs = [r for r in records if 'program' in r]
    return {
        'phases': {
            r['phase']: round(r['duration_s'], 1)
            for r in records if 'program' not in r
        },
        'programs': len(programs),
        'programs_from_cache': sum(bool(r['cache_hit']) for r in programs),
        'programs_s': round(sum(r['duration_s'] for r in programs), 1),
        # [program, seconds, from the cache, the dispatch it was met in,
        # lowered again for shapes it had run]
        'slowest_programs': [
            [r['program'], round(r['duration_s'], 1), bool(r['cache_hit']),
             f"{r.get('during')}:{r.get('seq')}", bool(r.get('relowered'))]
            for r in sorted(programs, key=lambda r: -r['duration_s'])[:8]
        ],
    }


def sample_for_check(state, ctx) -> float:
    """The greedy call of the check through the engine; keeps the scored
    rows' prompts and tokens for ``verify``. Returns the seconds it took
    (outside set-up and window)."""
    from distllm_tpu.generate.engine.engine import SamplingParams

    t = time.perf_counter()
    engine = state['engine']
    prompts = engine_closed._call_prompts(ctx, 'check')
    budget = int(ctx.traffic['output_tokens']['value'])
    recorded_before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=budget)
    )
    records = sorted(
        (r for r in _engine.flight_since(engine, recorded_before)
         if r['kind'] == 'request'),
        key=lambda r: r['request_id'],
    )  # in the order of ``prompts``: ids are given as requests are added
    by_length = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    rows = sorted({
        by_length[round(j * (len(by_length) - 1) / (CHECK_ROWS - 1))]
        for j in range(CHECK_ROWS)
    })
    # What each scored row left in layer 0's pages: its first block (written
    # by a prefill span) and its last (written token by token in decode).
    # A finished row's blocks keep what they held until their next holder
    # writes them; a row whose blocks were taken again within the call reads
    # as noise, which the median over the rows in ``verify`` passes over.
    pages = None
    if len(records) == len(prompts):
        ends = np.asarray([
            [records[i]['kv_first_block'], records[i]['kv_tail_block']]
            for i in rows
        ])
        pages = tuple(
            np.asarray(pool[0][ends], np.float32)  # [rows, 2, block, G, d]
            for pool in (engine.kv.k, engine.kv.v)
        )
    state['check'] = (
        [prompts[i] for i in rows], [outputs[i] for i in rows], pages
    )
    return time.perf_counter() - t


def _page_error(params, model, prompt, output, k_pages, v_pages) -> float:
    """How far layer 0's K and V rows in a row's first and last block lie
    from float32 (``reference_laguna.first_layer_kv``): the larger of K's and
    V's relative RMS error over the slots the row wrote."""
    tokens = np.asarray(list(prompt) + list(output)[:-1])
    block = k_pages.shape[1]
    last = (len(tokens) - 1) // block * block
    at = np.unique(np.concatenate([
        np.arange(min(block, len(tokens))), np.arange(last, len(tokens)),
    ]))
    which, slot = (at >= max(last, block)).astype(int), at % block
    # Two blocks of positions whatever the row's length: one compiled shape.
    padded = np.pad(at, (0, 2 * block - len(at)), mode='edge')
    want_k, want_v = (
        rows[:len(at)] for rows in reference_laguna.first_layer_kv(
            params, model, tokens[padded], padded
        )
    )
    return max(
        reference_laguna.kv_content_error(k_pages[which, slot], want_k),
        reference_laguna.kv_content_error(v_pages[which, slot], want_v),
    )


def measure(state, ctx) -> dict:
    """``engine_closed``'s window, then the device seconds by named scope
    of the traced slice, read while the profiler's files are still there."""
    from benchmarks.readers import hybrid, spans

    obs = engine_closed.measure(state, ctx)
    obs['scope_s'] = hybrid.collect_scope_seconds(ctx.capture)
    # Two engine metrics whose lists ``tests/test_spans_readers.py`` holds
    # to ``mistral7b.batch_generate`` alone, so this cell cannot join them:
    # the same readers over the same window, under ``detail``.
    state['window_engine'] = {
        'reprefill_share': spans.reprefill_share(ctx, obs),
        'serving_compile_ms': spans.serving_compile_ms(ctx, obs),
        'budget_deferrals': state['engine'].telemetry.get('budget_deferrals', 0),
    }
    return obs


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``_engine.verify``: the float32 reference at the
    configuration's widths scores each check row's prompt with the engine's
    own greedy tokens appended (prefill in chunks through both cache
    groups, then decode through them), one row at a time. Four limits of
    ``reference_laguna``, with their reasons there: every token within
    ``TOKEN_GAP_LIMIT_STD`` of the reference's largest logit at its
    position, the median over the rows of each row's largest gap within
    ``ROW_GAP_LIMIT_STD``, the mean gap of all positions within
    ``MEAN_GAP_LIMIT_STD`` (a window a block off), and layer 0's K and V
    pages of the rows within ``KV_CONTENT_LIMIT`` of float32 (the pool's
    precision)."""
    t_verify = time.perf_counter()
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs, pages = state['check']
    budget = int(ctx.traffic['output_tokens']['value'])
    lengths_ok = all(len(o) == budget for o in outputs) and pages is not None
    worst, typical, per_row = float('inf'), float('inf'), []
    mean, means = float('inf'), []
    page_error, page_errors = float('inf'), []
    if lengths_ok:
        params = _weights(ctx)
        page_errors = [
            _page_error(params, ctx.config, p, o, k, v)
            for p, o, k, v in zip(prompts, outputs, *pages)
        ]
        page_error = float(np.median(page_errors))
        widths = _reference_widths(ctx)
        for prompt, output in zip(prompts, outputs):
            tokens = list(prompt) + list(output)[:-1]
            ids = np.zeros((1, min(w for w in widths if w >= len(tokens))), np.int32)
            ids[0, :len(tokens)] = tokens
            at = len(prompt) - 1 + np.arange(len(output))[None]
            logits = reference_laguna.laguna_logits(params, ctx.config, ids, at)
            gaps = reference_laguna.token_gaps(logits, [output])
            per_row.append(float(gaps.max()))
            means.append(float(gaps.mean()))
        del params
        worst, typical = max(per_row), float(np.median(per_row))
        mean = float(np.mean(means))
    correct = (
        lengths_ok
        and worst <= reference_laguna.TOKEN_GAP_LIMIT_STD
        and typical <= reference_laguna.ROW_GAP_LIMIT_STD
        and mean <= reference_laguna.MEAN_GAP_LIMIT_STD
        and page_error <= reference_laguna.KV_CONTENT_LIMIT
        and obs['failed'] == 0
        and (ctx.rehearsal
             or state['attn_backend'] == ctx.config['expect_attn_backend'])
    )
    return correct, {
        'token_gap_max_std': worst,
        'token_gap_row_median_std': typical,
        'token_gap_by_row': [round(g, 4) for g in per_row],
        'token_gap_mean_std': mean,
        'token_gap_mean_by_row': [round(g, 5) for g in means],
        'kv_content_error': page_error,
        'kv_content_error_by_row': [round(e, 5) for e in page_errors],
        'check_prompt_tokens': [len(p) for p in prompts],
        'attn_backend': state['attn_backend'],
        'kv_pools': state['kv_pools'],
        'setup_split_s': state['setup_split_s'],
        # Outside set-up and window, inside the run's time limit.
        'check_s': {
            'greedy_call': round(state.get('excluded_s', 0.0), 1),
            'reference': round(time.perf_counter() - t_verify, 1),
        },
        'window_engine': state.get('window_engine'),
    }


close = _engine.close
