"""Closed loop over ``LLMEngine.generate_ids`` for ``deepseek_v3`` with
latent attention (kakaocorp Kanana-2-30B-A3B): ``engine_closed``'s loop and
window with this architecture's own model config, seeded weights and plain
reference; what differs from ``laguna_closed`` is the model, its weights,
its reference and the check's view of the pool (one latent plane, no V).
The cell's file names this driver; nothing else here knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from benchmarks import reference_deepseek_v3 as reference
from benchmarks.drivers import _engine, engine_closed, laguna_closed

# What no family's name enters is ``laguna_closed``'s: the rows the check
# scores (8 of a call's prompts, shortest to longest), the widths the
# reference's rows are padded to, set-up's compile seconds from the flight
# records, and the window with its scope seconds.
CHECK_ROWS = laguna_closed.CHECK_ROWS
_reference_widths = laguna_closed._reference_widths
_compile_seconds = laguna_closed._compile_seconds
measure = laguna_closed.measure


def _model_cfg(model: dict):
    from distllm_tpu.models import deepseek_v3

    return deepseek_v3.DeepseekV3Config.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import deepseek_v3

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(
        lambda: deepseek_v3.init_on_device(jax.random.PRNGKey(0), cfg)
    )


def _compile_reference_ahead(ctx, split: dict) -> None:
    """On a thread beside the engine's set-up: the reference's programs
    compiled from shapes into the compile cache (``compile_ahead``), where
    ``verify`` finds them. With no cache they are 8-10 s each, after the
    window, inside the run's time limit; the host has cores to spare while
    the main thread builds and warms the engine. A failure here costs
    ``verify`` that time again and nothing else."""
    t = time.perf_counter()
    try:
        reference.compile_ahead(
            ctx.config, _weight_shapes(ctx), _reference_widths(ctx),
            scored=int(ctx.traffic['output_tokens']['value']),
            kv_rows=2 * ctx.config['engine']['block_size'],
        )
        split['reference_ahead'] = round(time.perf_counter() - t, 1)
    except Exception as exc:  # noqa: BLE001 -- the check compiles them itself
        split['reference_ahead'] = f'failed: {exc!r}'[:200]


def _weights(ctx):
    """The program's parameter tree (``deepseek_v3.init_on_device``'s shapes
    and types), filled on the device in one jitted call that takes the key
    as an ARGUMENT, so that every seed finds one compiled program: normal(0,
    0.02) kernels and selection bias (the configuration's ``assumed``), unit
    norm scales."""
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
    import distllm_tpu.models.deepseek_v3  # noqa: F401 -- fail first

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
    # What each scored row left in layer 0's plane: its first block (written
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
        # [rows, 2, block, stored row]: the one KV head of the host's view
        pages = np.asarray(engine.kv.k[0][ends], np.float32)[..., 0, :]
    state['check'] = (
        [prompts[i] for i in rows], [outputs[i] for i in rows], pages
    )
    return time.perf_counter() - t


def _page_error(params, model, prompt, output, pages) -> float:
    """How far layer 0's latent rows in a row's first and last block lie
    from float32 (``reference.first_layer_rows``): the relative RMS error
    over the slots the row wrote, of the lanes a row uses."""
    tokens = np.asarray(list(prompt) + list(output)[:-1])
    block = pages.shape[1]
    last = (len(tokens) - 1) // block * block
    at = np.unique(np.concatenate([
        np.arange(min(block, len(tokens))), np.arange(last, len(tokens)),
    ]))
    which, slot = (at >= max(last, block)).astype(int), at % block
    # Two blocks of positions whatever the row's length: one compiled shape.
    padded = np.pad(at, (0, 2 * block - len(at)), mode='edge')
    want = reference.first_layer_rows(
        params, model, tokens[padded], padded
    )[:len(at)]
    return reference.row_content_error(
        pages[which, slot, :want.shape[-1]], want
    )


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``_engine.verify``: the float32 reference at the
    configuration's widths scores each check row's prompt with the engine's
    own greedy tokens appended (prefill in chunks through the latent pool,
    then decode through it), one row at a time. Four limits of
    ``reference_deepseek_v3``, with their reasons there: every token within
    ``TOKEN_GAP_LIMIT_STD`` of the reference's largest logit at its
    position, the median over the rows of each row's largest gap within
    ``ROW_GAP_LIMIT_STD``, the mean gap of all positions within
    ``MEAN_GAP_LIMIT_STD``, and layer 0's latent rows of the scored requests
    within ``ROW_CONTENT_LIMIT`` of float32 (the pool's precision)."""
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
            _page_error(params, ctx.config, p, o, rows)
            for p, o, rows in zip(prompts, outputs, pages)
        ]
        page_error = float(np.median(page_errors))
        widths = _reference_widths(ctx)
        for prompt, output in zip(prompts, outputs):
            tokens = list(prompt) + list(output)[:-1]
            ids = np.zeros((1, min(w for w in widths if w >= len(tokens))), np.int32)
            ids[0, :len(tokens)] = tokens
            at = len(prompt) - 1 + np.arange(len(output))[None]
            logits = reference.deepseek_logits(params, ctx.config, ids, at)
            gaps = reference.token_gaps(logits, [output])
            per_row.append(float(gaps.max()))
            means.append(float(gaps.mean()))
        del params
        worst, typical = max(per_row), float(np.median(per_row))
        mean = float(np.mean(means))
    correct = (
        lengths_ok
        and worst <= reference.TOKEN_GAP_LIMIT_STD
        and typical <= reference.ROW_GAP_LIMIT_STD
        and mean <= reference.MEAN_GAP_LIMIT_STD
        and page_error <= reference.ROW_CONTENT_LIMIT
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
