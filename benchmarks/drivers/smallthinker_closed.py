"""Closed loop over ``LLMEngine.generate_ids`` for ``smallthinker``
(PowerInfer SmallThinker-21BA3B-Instruct): ``laguna_closed``'s loop and
window with this architecture's own model config, seeded weights, plain
reference and check. The cell's file names this driver; nothing else here
knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from benchmarks import reference_smallthinker as reference
from benchmarks.drivers import _engine, engine_closed, laguna_closed

# The check runs at the cell's load: one greedy call of a whole call's
# prompts with the cell's output budget, through the timed path. CHECK_ROWS
# of them, evenly spaced by prompt length from the shortest to the longest
# (of the cell's 48: three that stay under the window of 4096 for their
# whole life, one that crosses it while it decodes, four past it), are
# scored by the reference: every token of each.
CHECK_ROWS = 8
# The layers whose pages are read back: layer 0 (the first of the full
# group) and layer 1 (the first of the window group).
FULL_LAYER, WINDOW_LAYER = 0, 1


def _model_cfg(model: dict):
    from distllm_tpu.models import smallthinker

    return smallthinker.SmallThinkerConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import smallthinker

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(
        lambda: smallthinker.init_on_device(jax.random.PRNGKey(0), cfg)
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
    ``verify`` finds them. A failure here costs ``verify`` that time again
    and nothing else."""
    t = time.perf_counter()
    try:
        reference.compile_ahead(
            ctx.config, _weight_shapes(ctx), _reference_widths(ctx),
            scored=int(ctx.traffic['output_tokens']['value']),
        )
        split['reference_ahead'] = round(time.perf_counter() - t, 1)
    except Exception as exc:  # noqa: BLE001 -- the check compiles them itself
        split['reference_ahead'] = f'failed: {exc!r}'[:200]


def _weights(ctx):
    """The program's parameter tree (``smallthinker.init_on_device``'s
    shapes and types), filled on the device in one jitted call that takes
    the key as an ARGUMENT, so that every seed finds one compiled program:
    normal(0, 0.02) kernels, unit norm scales."""
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
        'moe_form': engine.telemetry.get('moe_form'),
        'setup_split_s': {
            'weights': round(t1 - t0, 1),
            'engine': round(time.perf_counter() - t1, 1),
        },
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.smallthinker  # noqa: F401 -- fail first

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
    state['setup_split_s'].update(
        laguna_closed._compile_seconds(engine), **ahead_split
    )
    return state


def _ends(record: dict, block: int) -> dict | None:
    """Where the two blocks a group a finished request names lie: ``group ->
    ([first block, tail block], the first token position of each)``. None
    where the record lacks one (a request that wrote nothing)."""
    names = ('kv_first_block', 'kv_tail_block', 'kv_window_first_index',
             'kv_window_first_block', 'kv_window_tail_block')
    if any(n not in record for n in names):
        return None
    written = record['prompt_tokens'] + record['output_tokens'] - 1
    tail = (written - 1) // block * block
    return {
        'full': ([record['kv_first_block'], record['kv_tail_block']], [0, tail]),
        'window': (
            [record['kv_window_first_block'], record['kv_window_tail_block']],
            [record['kv_window_first_index'] * block, tail],
        ),
    }


def sample_for_check(state, ctx) -> float:
    """The greedy call of the check through the engine; keeps the scored
    rows' prompts and tokens for ``verify``, and what each left in the
    pages of ``FULL_LAYER`` and ``WINDOW_LAYER``: of each group its first
    block still held (the full group's: positions 0 onward, written by a
    prefill span; the window group's: the window's lower edge) and the block
    of the last position written (token by token in decode). A finished
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
    rows = sorted({
        by_length[round(j * (len(by_length) - 1) / (CHECK_ROWS - 1))]
        for j in range(CHECK_ROWS)
    })
    pages = None
    block = ctx.config['engine']['block_size']
    ends = [_ends(records[i], block) for i in rows] if (
        len(records) == len(prompts)
    ) else [None]
    if all(e is not None for e in ends):
        pools = {
            'full': (engine.kv, 0), 'window': (engine.window_kv, 0),
        }  # FULL_LAYER and WINDOW_LAYER are each the first of their group
        pages = [
            {
                group: (
                    at,
                    *(np.asarray(side[layer][np.asarray(blocks)], np.float32)
                      for side in (pool.k, pool.v)),  # [2, block, G, d] each
                )
                for group, (pool, layer) in pools.items()
                for blocks, at in [row_ends[group]]
            }
            for row_ends in ends
        ]
    state['check'] = (
        [prompts[i] for i in rows], [outputs[i] for i in rows], pages
    )
    state['check_preemptions'] = sum(r['kind'] == 'preempt' for r in flight)
    return time.perf_counter() - t


def _page_errors(pages: dict, kept: dict, written: int) -> dict:
    """``group -> error`` of one row: how far the K and V rows of the
    group's two blocks lie from the reference's (``kept``: the reference's
    K and V of ``FULL_LAYER`` and ``WINDOW_LAYER`` at every position), over
    the slots the row wrote, the larger of K's and V's.

    ``full`` is the relative RMS error: layer 0 reads the embedding alone,
    so its K and V are a function of a token and its position, and the
    pool's CONTENT can be held to float32 without the program's noise from
    below: the precision limit. ``window`` is the MEDIAN over the positions
    of each position's relative error: layer 1 lies behind layer 0's
    routed experts, and where bfloat16 turns over a token's sixth and
    seventh choice (one token in some tens) that token's stream, and so its
    K and V, is another: an RMS would read the flips, the median position
    reads what the pool holds."""
    out = {}
    for group, layer in (('full', FULL_LAYER), ('window', WINDOW_LAYER)):
        at, k_pages, v_pages = pages[group]
        block = k_pages.shape[1]
        positions = np.concatenate([a + np.arange(block) for a in at])
        slots = np.flatnonzero(positions < written)
        if at[0] == at[1]:
            slots = slots[: len(slots) // 2]  # one block, named twice
        errors = []
        for held, want in ((k_pages, kept[layer]['k']), (v_pages, kept[layer]['v'])):
            held = held.reshape(-1, *held.shape[2:])[slots]
            want = want[positions[slots]]
            if group == 'full':
                errors.append(reference.kv_content_error(held, want))
            else:
                errors.append(float(np.median(
                    np.sqrt(((held - want) ** 2).sum((1, 2))
                            / (want ** 2).sum((1, 2)))
                )))
        out[group] = max(errors)
    return out


def measure(state, ctx) -> dict:
    """``engine_closed``'s window, then, from ONE parse of the profiler's
    file while it is still there, the device seconds by named scope
    (``hybrid.scope_seconds``) and of the kernel calls by program and scope
    (``readers/lfm2.kernel_seconds``). A traced run's window is the traced
    call alone, as ``lfm2_closed.measure`` says why: a trace covers a whole
    call, the profiler's stop and the trace's parses hold the run for over a
    minute, and no per-layer metric reads a second call."""
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
        # device seconds of kernel calls by '<program> <scope>' (traced runs)
        'kernel_call_s': obs['kernel_call_s'],
    }
    return obs


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``laguna_closed.verify``: the float32 reference at
    the configuration's widths scores each check row's prompt with the
    engine's own greedy tokens appended (prefill in chunks through both
    cache groups, then decode through them), one row at a time, and in the
    same pass gives the keys and values of ``FULL_LAYER`` and
    ``WINDOW_LAYER``. Five limits of ``reference_smallthinker``, with their
    reasons there: every token within ``TOKEN_GAP_LIMIT_STD`` of the
    reference's largest logit at its position, the median over the rows of
    each row's largest gap within ``ROW_GAP_LIMIT_STD``, the mean gap of all
    positions within ``MEAN_GAP_LIMIT_STD`` (a window too short), the full
    group's pages within ``KV_CONTENT_LIMIT`` of float32 (the pool's
    precision) and the window group's within ``KV_WINDOW_CONTENT_LIMIT``
    (what the pool holds at the window's two ends)."""
    t_verify = time.perf_counter()
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs, pages = state['check']
    budget = int(ctx.traffic['output_tokens']['value'])
    lengths_ok = all(len(o) == budget for o in outputs) and pages is not None
    inf = float('inf')
    worst = typical = mean = inf
    page_error = {'full': inf, 'window': inf}
    per_row, means, page_errors = [], [], []
    if lengths_ok:
        params = _weights(ctx)
        widths = _reference_widths(ctx)
        for prompt, output, row_pages in zip(prompts, outputs, pages):
            tokens = list(prompt) + list(output)[:-1]
            ids = np.zeros((1, min(w for w in widths if w >= len(tokens))), np.int32)
            ids[0, :len(tokens)] = tokens
            at = len(prompt) - 1 + np.arange(len(output))[None]
            gaps, kept = reference.smallthinker_token_gaps(
                params, ctx.config, ids, at, [output],
                keep=(FULL_LAYER, WINDOW_LAYER), fields=('k', 'v'),
            )
            per_row.append(float(gaps.max()))
            means.append(float(gaps.mean()))
            page_errors.append(_page_errors(row_pages, kept[0], len(tokens)))
        del params
        worst, typical = max(per_row), float(np.median(per_row))
        mean = float(np.mean(means))
        page_error = {
            g: float(np.median([e[g] for e in page_errors])) for g in page_error
        }
    correct = (
        lengths_ok
        and worst <= reference.TOKEN_GAP_LIMIT_STD
        and typical <= reference.ROW_GAP_LIMIT_STD
        and mean <= reference.MEAN_GAP_LIMIT_STD
        and page_error['full'] <= reference.KV_CONTENT_LIMIT
        and page_error['window'] <= reference.KV_WINDOW_CONTENT_LIMIT
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
        'kv_content_error': page_error['full'],
        'kv_window_content_error': page_error['window'],
        'kv_content_error_by_row': [
            [round(e['full'], 5), round(e['window'], 5)] for e in page_errors
        ],
        'check_prompt_tokens': [len(p) for p in prompts],
        'check_preemptions': state.get('check_preemptions'),
        'attn_backend': state['attn_backend'],
        'kv_pools': state['kv_pools'],
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
