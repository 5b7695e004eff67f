"""Closed loop over ``LLMEngine.generate_ids`` for ``sdar_moe`` (JetLM
SDAR-30B-A3B-Chat: generation by diffusion over blocks): ``engine_closed``'s
loop and window with this architecture's own model config, seeded weights,
plain reference and check. The cell's file names this driver; nothing else
here knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from benchmarks import reference_sdar as reference
from benchmarks.drivers import _engine, engine_closed, laguna_closed

# The check runs at the cell's load: one greedy call of a whole call's
# prompts with the cell's output budget, through the timed path. CHECK_ROWS
# of them, evenly spaced by prompt length from the shortest to the longest,
# are scored by the reference: the first, the middle and the last WHOLE
# block of each (a last block cut by the budget has positions nobody was
# given), every denoise step of each.
CHECK_ROWS = 8
FIRST_LAYER = 0


def _model_cfg(model: dict):
    from distllm_tpu.models import sdar

    return sdar.SdarConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import sdar

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(
        lambda: sdar.init_on_device(jax.random.PRNGKey(0), cfg)
    )


def _weights(ctx):
    """The program's parameter tree (``sdar.init_on_device``'s shapes and
    types), filled on the device in one jitted call that takes the key as an
    ARGUMENT, so that every seed finds one compiled program: normal(0, 0.02)
    kernels, unit norm scales. The key is of the ``rbg`` generator: the
    default one's program for 5.2 G draws took 31 s to compile on the chip
    (4 s this one; my chip runs, PR 54), a tenth of a cold run's limit."""
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

    return fill(jax.random.key(ctx.seed % (2**31), impl='rbg'))


def _reference_widths(ctx) -> list[int]:
    """A reference forward is padded on the right (keys past the sequence
    are masked) to a multiple of 256 positions, so that a handful of shapes
    is compiled and not one a length."""
    longest = ctx.config['engine']['max_model_len']
    step = min(256, longest)
    return list(range(step, longest + 1, step))


def _longest_scored(ctx) -> int:
    """The longest sequence a reference forward of the check can see."""
    spec = ctx.traffic
    sizes = spec['prompt_tokens']
    longest = int(sizes.get('hi', sizes.get('value', 0)))
    return longest + int(spec['output_tokens']['value'])


def _compile_reference_ahead(ctx, split: dict) -> None:
    """On a thread beside the engine's set-up: the reference's programs
    compiled from shapes into the compile cache (``compile_ahead``), where
    ``verify`` finds them. A failure here costs ``verify`` that time again
    and nothing else."""
    t = time.perf_counter()
    try:
        longest = _longest_scored(ctx)
        reference.compile_ahead(
            ctx.config, _weight_shapes(ctx),
            [w for w in _reference_widths(ctx) if w - 256 < longest],
            keep=(FIRST_LAYER, ctx.config['num_hidden_layers'] - 1),
        )
        split['reference_ahead'] = round(time.perf_counter() - t, 1)
    except Exception as exc:  # noqa: BLE001 -- the check compiles them itself
        split['reference_ahead'] = f'failed: {exc!r}'[:200]


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
        'moe_form': engine.telemetry.get('moe_form'),
        'setup_split_s': {
            'weights': round(t1 - t0, 1),
            'engine': round(time.perf_counter() - t1, 1),
        },
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.sdar  # noqa: F401 -- fail first

    ahead_split: dict = {}
    ahead = threading.Thread(
        target=_compile_reference_ahead, args=(ctx, ahead_split), daemon=True
    )
    ahead.start()
    state = build(ctx)
    engine = state['engine']
    budget = int(ctx.traffic['output_tokens']['value'])
    t = time.perf_counter()
    warmups = int(ctx.workload.get('warmup', {}).get('replica_calls', 1))
    for n in range(warmups):
        engine.generate_ids(
            engine_closed._call_prompts(ctx, f'warmup{n}'),
            _engine.sampling(ctx, budget),
        )
    state['setup_split_s']['warmup_calls'] = round(time.perf_counter() - t, 1)
    check_s = sample_for_check(state, ctx)
    # With no warm-up call of its own (``replica_calls`` 0) the check's
    # greedy call IS the warm-up, the first run of every program the window
    # dispatches (a greedy and a sampled call run the same programs: the
    # draw is a branch inside them): it then counts as set-up.
    state['excluded_s'] = check_s if warmups else 0.0
    state['setup_split_s']['check_call'] = round(check_s, 1)
    ahead.join()  # never beside the window
    state['setup_split_s'].update(
        laguna_closed._compile_seconds(engine), **ahead_split
    )
    return state


def _pages(engine, record: dict, layers) -> dict | None:
    """``layer -> (k, v)``, each ``[2, block, G, d]`` float32: the request's
    first page and the page of the last whole block of its tokens, as the
    pool holds them."""
    if 'kv_first_block' not in record or 'kv_tail_block' not in record:
        return None
    blocks = np.asarray([record['kv_first_block'], record['kv_tail_block']])
    return {
        layer: tuple(
            np.asarray(side[layer][blocks], np.float32)
            for side in (engine.kv.k, engine.kv.v)
        )
        for layer in layers
    }


def sample_for_check(state, ctx) -> float:
    """The greedy call of the check through the engine; keeps the scored
    rows' prompts, tokens and decided-at steps for ``verify``, and what each
    left in the pages of the first and the last layer: its first page
    (written by a prefill span, or given in its first block) and the page of
    the last whole block of its tokens (decided, then committed). A finished
    row's blocks keep what they held until their next holder writes them; a
    row whose blocks were taken again within the call reads as noise, which
    the median over the rows in ``verify`` passes over. Returns the seconds
    it took (outside set-up and window)."""
    from distllm_tpu.generate.engine.engine import SamplingParams

    t = time.perf_counter()
    engine = state['engine']
    prompts = engine_closed._call_prompts(ctx, 'check')
    budget = int(ctx.traffic['output_tokens']['value'])
    recorded_before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=budget)
    )
    flight = _engine.flight_since(engine, recorded_before)
    records = sorted(
        (r for r in flight if r['kind'] == 'request'),
        key=lambda r: r['request_id'],
    )  # in the order of ``prompts``: ids are given as requests are added
    by_length = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    count = min(CHECK_ROWS, len(prompts))
    rows = sorted({
        by_length[round(j * (len(by_length) - 1) / max(1, count - 1))]
        for j in range(count)
    })
    layers = (FIRST_LAYER, ctx.config['num_hidden_layers'] - 1)
    kept = None
    if len(records) == len(prompts):
        kept = [
            (records[i].get('decided_at'), _pages(engine, records[i], layers))
            for i in rows
        ]
        if any(at is None or pages is None for at, pages in kept):
            kept = None
    state['check'] = (
        [prompts[i] for i in rows], [outputs[i] for i in rows], kept
    )
    state['check_preemptions'] = sum(r['kind'] == 'preempt' for r in flight)
    return time.perf_counter() - t


def measure(state, ctx) -> dict:
    """``engine_closed``'s window, then, from ONE parse of the profiler's
    file while it is still there, the device seconds by named scope
    (``hybrid.scope_seconds``) and of the kernel calls by program and scope
    (``readers/lfm2.kernel_seconds``). A traced run's window is the traced
    call alone, as ``lfm2_closed.measure`` says why."""
    from benchmarks.readers import hybrid, lfm2

    if ctx.capture.length_s > 0:
        ctx = dataclasses.replace(
            ctx, seconds=min(ctx.seconds, ctx.capture.length_s)
        )
    obs = engine_closed.measure(state, ctx)
    xspace = lfm2.load_xspace(ctx.capture)
    obs['scope_s'] = obs['kernel_call_s'] = None
    if xspace is not None:
        try:
            obs['scope_s'] = hybrid.scope_seconds(xspace)
            obs['kernel_call_s'] = lfm2.kernel_seconds(xspace)
        except Exception:  # noqa: BLE001 -- a metric left out, never a failed run
            pass
    flight = obs['flight']
    state['window_engine'] = {
        'preemptions': sum(r['kind'] == 'preempt' for r in flight),
        'budget_deferrals': state['engine'].telemetry.get('budget_deferrals', 0),
        'kernel_call_s': obs['kernel_call_s'],
    }
    return obs


def scored_blocks(n_prompt: int, n_output: int, block: int) -> list[int]:
    """The starts of the blocks the check replays of a row: the first block
    its window decided, the last whole block of its tokens, and the one
    half-way between."""
    first = n_prompt // block * block
    last = (n_prompt + n_output) // block * block - block
    middle = (first + last) // 2 // block * block
    return sorted({first, middle, max(first, last)})


def score_row(params, model, prompt, output, decided_at, pages, widths,
              dtype='float32') -> dict:
    """One row against the reference: for each scored block and each denoise
    step the gaps of the tokens decided at that step, how far the position
    the program decided lies under the reference's most confident masked
    one, and (``pages``) what the pool holds against the reference's K and V
    of the first and the last layer."""
    block, steps = model['block_length'], model['engine']['denoise_steps']
    known = list(prompt) + list(output)
    at_all = [-1] * len(prompt) + list(decided_at)
    width = lambda n: min(w for w in widths if w >= n)  # noqa: E731
    # every forward keeps both layers' K and V: one program a width
    layers = (FIRST_LAYER, model['num_hidden_layers'] - 1)
    gaps, shortfalls = [], []
    for start in scored_blocks(len(prompt), len(output), block):
        tokens = np.asarray(known[start:start + block])
        at = np.asarray(at_all[start:start + block])
        given = tokens[at < 0]
        for s, (logits, masked) in enumerate(reference.replay_block(
            params, model, known[:start], list(given), tokens, at, steps,
            width=width(start + block), dtype=dtype, keep=layers,
        )):
            decided = at == s
            if not decided.any():
                continue
            gaps += list(reference.token_gaps(logits[decided], tokens[decided]))
            conf = np.asarray([
                reference.confidence(logits[i], int(logits[i].argmax()))
                for i in range(block)
            ])
            best = conf[masked].max()
            shortfalls += list((best - conf[decided]) / best)
    out = {'gaps': gaps, 'shortfalls': shortfalls}
    if pages is not None:
        whole = len(known) // block * block
        _, kept = reference.forward(
            params, model, known[:whole], width=width(whole), keep=layers,
            dtype=dtype,
        )
        page = model['engine']['block_size']
        starts = [0, (whole - 1) // page * page]
        for layer in layers:
            errors = []
            for side in (0, 1):
                held = np.concatenate([
                    pages[layer][side][i][: max(0, min(page, whole - at))]
                    for i, at in enumerate(starts)
                ])
                want = np.concatenate([
                    np.asarray(kept[layer][side])[at:at + page] for at in starts
                ])
                errors.append(reference.kv_content_error(held, want))
            out[f'kv_layer_{layer}'] = max(errors)
    return out


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """The float32 reference at the configuration's widths replays the first,
    middle and last whole block of each check row step by step from the
    recorded decided-at steps, and holds every decided token and every kept
    position to its own logits and confidences, and the pages of the first
    and the last layer to its K and V. Five limits of ``reference_sdar``,
    with their reasons there and their readings in ``benchmarks/SDAR.md``."""
    t_verify = time.perf_counter()
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs, kept = state['check']
    budget = int(ctx.traffic['output_tokens']['value'])
    lengths_ok = all(len(o) == budget for o in outputs) and kept is not None
    inf = float('inf')
    worst = mean = shortfall = first_error = last_error = inf
    rows: list[dict] = []
    if lengths_ok:
        params = _weights(ctx)
        widths = _reference_widths(ctx)
        for prompt, output, (decided_at, pages) in zip(prompts, outputs, kept):
            rows.append(score_row(
                params, ctx.config, prompt, output, decided_at, pages, widths
            ))
        del params
        gaps = np.concatenate([r['gaps'] for r in rows])
        worst, mean = float(gaps.max()), float(gaps.mean())
        shortfall = float(np.mean(np.concatenate([r['shortfalls'] for r in rows])))
        last = ctx.config['num_hidden_layers'] - 1
        first_error = float(np.median([r[f'kv_layer_{FIRST_LAYER}'] for r in rows]))
        last_error = float(np.median([r[f'kv_layer_{last}'] for r in rows]))
    correct = (
        lengths_ok
        and worst <= reference.TOKEN_GAP_LIMIT_STD
        and mean <= reference.MEAN_GAP_LIMIT_STD
        and shortfall <= reference.CONFIDENCE_LIMIT
        and first_error <= reference.KV_CONTENT_LIMIT
        and last_error <= reference.KV_LAST_CONTENT_LIMIT
        and obs['failed'] == 0
        and (ctx.rehearsal
             or state['attn_backend'] == ctx.config['expect_attn_backend'])
    )
    return correct, {
        'token_gap_max_std': worst,
        'token_gap_mean_std': mean,
        'confidence_shortfall_mean': shortfall,
        'kv_content_error': first_error,
        'kv_last_content_error': last_error,
        'by_row': [
            [round(float(np.max(r['gaps'])), 4), round(float(np.mean(r['gaps'])), 5),
             round(float(np.mean(r['shortfalls'])), 5),
             *(round(v, 5) for k, v in sorted(r.items()) if k.startswith('kv_'))]
            for r in rows
        ],
        'check_prompt_tokens': [len(p) for p in prompts],
        'check_preemptions': state.get('check_preemptions'),
        'attn_backend': state['attn_backend'],
        'moe_form': state.get('moe_form'),
        'setup_split_s': state['setup_split_s'],
        # Outside set-up and window, inside the run's time limit.
        'check_s': {
            'greedy_call': round(state.get('excluded_s', 0.0), 1),
            'reference': round(time.perf_counter() - t_verify, 1),
        },
        'window_engine': state.get('window_engine'),
    }


close = _engine.close
