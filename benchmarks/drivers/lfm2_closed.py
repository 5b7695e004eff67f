"""Closed loop over ``LLMEngine.generate_ids`` for ``lfm2_moe`` (LiquidAI
LFM2-8B-A1B): ``engine_closed``'s loop and window with this architecture's
own model config, seeded weights and plain reference; what differs from
``deepseek_v3_closed`` is the model, its weights, its reference and the
check's view of what a sequence holds (a slot of the state pool beside K and
V pages of the attention layers). The cell's file names this driver; nothing
else here knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from benchmarks import reference_lfm2 as reference
from benchmarks.drivers import _engine, engine_closed, laguna_closed
from benchmarks.readers import lfm2 as lfm2_reader

# What no family's name enters is ``laguna_closed``'s: the rows the check
# scores (8 of a call's prompts, shortest to longest) and the widths the
# reference's rows are padded to.
CHECK_ROWS = laguna_closed.CHECK_ROWS
_reference_widths = laguna_closed._reference_widths

# Standard deviations of the seeded leaves that are not normal(0, 0.02),
# by the leaf's own key (the configuration's ``assumed`` 6 and 7).
_LEAF_STD = {'taps': 3 ** -0.5, 'bias': 0.05}


def _model_cfg(model: dict):
    from distllm_tpu.models import lfm2

    return lfm2.Lfm2MoeConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import lfm2

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(
        lambda: lfm2.init_on_device(jax.random.PRNGKey(0), cfg)
    )


def _kv_rows(ctx) -> int:
    """Tokens a call of ``reference.first_attn_kv`` takes: a block of pages
    and the tokens before it that its rows depend on, in one shape."""
    return 2 * ctx.config['engine']['block_size']


def _compile_reference_ahead(ctx) -> None:
    """On a thread beside the engine's set-up: the reference's programs
    compiled from shapes into the compile cache (``compile_ahead``), where
    ``verify`` finds them. With no cache they are 8-10 s each, after the
    window, inside the run's time limit; the host has cores to spare while
    the main thread builds and warms the engine. A failure here costs
    ``verify`` that time again and nothing else."""
    try:
        reference.compile_ahead(
            ctx.config, _weight_shapes(ctx), _reference_widths(ctx),
            scored=len(_scored(ctx)), kv_rows=_kv_rows(ctx),
        )
    except Exception:  # noqa: BLE001 -- the check compiles them itself
        pass


def _weights(ctx):
    """The program's parameter tree (``lfm2.init_on_device``'s shapes and
    types), filled on the device in one jitted call that takes the key as
    an ARGUMENT, so that every seed finds one compiled program: unit norm
    scales, normal(0, 0.02) kernels, the taps and the selection bias at
    ``_LEAF_STD``."""
    shapes = _weight_shapes(ctx)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    jnp = jax.numpy

    @jax.jit
    def fill(key):
        leaves = []
        for sub, (path, leaf) in zip(jax.random.split(key, len(paths)), paths):
            name = str(getattr(path[-1], 'key', ''))
            if name == 'scale':
                value = jnp.ones(leaf.shape, jnp.float32)
            else:
                value = jax.random.normal(sub, leaf.shape, jnp.float32) * (
                    _LEAF_STD.get(name, 0.02)
                )
            leaves.append(value.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return fill(jax.random.PRNGKey(ctx.seed % (2**31)))


def build(ctx) -> dict:
    from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine

    model = ctx.config
    engine = LLMEngine(
        _model_cfg(model), _weights(ctx), _engine._NoTokenizer(),
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
    return {
        'engine': engine, 'attn_backend': backend,
        'kv_pools': engine.telemetry['kv_pools'],
        'kv_walk_keys': engine.telemetry.get('kv_walk_keys'),
        'state_pool': engine.telemetry['state_pool'],
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.lfm2  # noqa: F401 -- fail first

    ahead = threading.Thread(
        target=_compile_reference_ahead, args=(ctx,), daemon=True
    )
    ahead.start()
    state = build(ctx)
    engine = state['engine']
    budget = int(ctx.traffic['output_tokens']['value'])
    for n in range(int(ctx.workload.get('warmup', {}).get('replica_calls', 1))):
        engine.generate_ids(
            engine_closed._call_prompts(ctx, f'warmup{n}'),
            _engine.sampling(ctx, budget),
        )
    state['excluded_s'] = sample_for_check(state, ctx)
    ahead.join()  # never beside the window
    return state


def sample_for_check(state, ctx) -> float:
    """The greedy call of the check through the engine; keeps the scored
    rows' prompts and tokens, and what each left in the pools, for
    ``verify``. Returns the seconds it took (outside set-up and window)."""
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
    # What each scored row left behind. In its slot of the state pool, the
    # first conv layer's two rows; in the first attention layer's pages,
    # its first block (written by a prefill span) and its last (written
    # token by token in decode). A freed slot or block keeps what it held
    # until its next holder writes it, and a call of as many prompts as
    # slots gives none a second holder: every scored row's are its own, so
    # ``verify`` holds the LARGEST row to a limit too.
    held = None
    if len(records) == len(prompts):
        slots = np.asarray([records[i]['state_slot'] for i in rows])
        ends = np.asarray([
            [records[i]['kv_first_block'], records[i]['kv_tail_block']]
            for i in rows
        ])
        held = (
            np.asarray(engine.state_pool.state['conv'][0][slots], np.float32),
            # [rows, 2, block, kv heads, d] of the first attention layer
            np.asarray(engine.kv.k[0][ends], np.float32),
            np.asarray(engine.kv.v[0][ends], np.float32),
        )
    state['check'] = (
        [prompts[i] for i in rows], [outputs[i] for i in rows], held
    )
    return time.perf_counter() - t


def _scored(ctx) -> np.ndarray:
    """Which of a row's generated tokens the reference scores."""
    budget = int(ctx.traffic['output_tokens']['value'])
    return np.arange(0, budget, reference.SCORE_EVERY)


def _state_error(params, model, fed, rows) -> float:
    """How far the first conv layer's state in a row's slot lies from
    float32: ``u`` of the last two tokens the row was fed."""
    want = reference.first_conv_inputs(params, model, np.asarray(fed[-2:]))
    return reference.content_error(rows[-len(want):], want)


def _kv_error(params, model, fed, k_pages, v_pages, kv_rows) -> tuple:
    """How far the first attention layer's K and V rows in a row's first
    and last block lie from float32 (``reference.first_attn_kv``): ``(K's,
    V's)`` relative RMS error over the slots the row wrote, the larger of
    the two blocks' each."""
    tokens = np.asarray(fed)
    block, before = k_pages.shape[1], reference.receptive_tokens(model)
    last = (len(tokens) - 1) // block * block
    errors = []
    for which, first in ((0, 0), (1, last)):
        # A run that starts ``before`` tokens ahead of the block (or at
        # position 0, from no state), padded behind to one compiled shape:
        # what follows a position cannot reach it.
        start = max(first - before, 0)
        run = np.zeros((kv_rows,), tokens.dtype)
        got = tokens[start:start + kv_rows]
        run[:len(got)] = got
        want = reference.first_attn_kv(
            params, model, run, start + np.arange(kv_rows)
        )
        lo, hi = first - start, min(first + block, len(tokens)) - start
        errors.append([
            reference.content_error(pages[which, :hi - lo], rows[lo:hi])
            for pages, rows in zip((k_pages, v_pages), want)
        ])
    return tuple(np.max(errors, axis=0))


def measure(state, ctx) -> dict:
    """``engine_closed``'s window, then, from ONE parse of the profiler's
    file while it is still there, the device seconds by named scope
    (``hybrid.scope_seconds``) and of the kernel calls by program and scope
    (``readers/lfm2.kernel_seconds``).

    A traced run's window is the traced call alone. A trace covers a whole
    call, and stopping the profiler after one takes 50 s whatever the
    call's length, all inside the window; ``engine_closed``'s loop would
    then run one more call, which no per-layer metric reads (every call of
    a cell is the same sizes in the same order) and a traced run prints no
    ``gen_tok_s``. At 128 rows that call was 29 of a cold traced run's
    377-386 s, over the 360 s a run is given; at 96 rows and without it the
    run takes 327 s (my chip runs, PR 39)."""
    from benchmarks.readers import hybrid

    if ctx.capture.length_s > 0:
        ctx = dataclasses.replace(
            ctx, seconds=min(ctx.seconds, ctx.capture.length_s)
        )
    obs = engine_closed.measure(state, ctx)
    xspace = lfm2_reader.load_xspace(ctx.capture)
    obs['scope_s'] = obs['kernel_call_s'] = None
    if xspace is not None:
        try:
            obs['scope_s'] = hybrid.scope_seconds(xspace)
            obs['kernel_call_s'] = lfm2_reader.kernel_seconds(xspace)
        except Exception:  # noqa: BLE001 -- a metric left out, never a failed run
            pass
    state['kernel_call_s'] = obs['kernel_call_s']  # into the line's detail
    return obs


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``_engine.verify``: the float32 reference at the
    configuration's widths scores each check row's prompt with the engine's
    own greedy tokens appended (prefill in chunks through the pages and the
    state, then decode through them), one row at a time, every
    ``SCORE_EVERY``-th generated token of it. Six limits of
    ``reference_lfm2``, with their reasons in ``benchmarks/LFM2.md``: every
    scored token within ``TOKEN_GAP_LIMIT_STD`` of the reference's largest
    logit at its position, the median over the rows of each row's largest
    gap within ``ROW_GAP_LIMIT_STD``, the mean gap of all scored positions
    within ``MEAN_GAP_LIMIT_STD``, the first conv layer's state in EVERY
    row's slot within ``STATE_CONTENT_LIMIT`` of float32, and of the first
    attention layer's K and V pages the median over the rows within
    ``KV_CONTENT_LIMIT`` (the pool's precision) and every row within
    ``KV_ROW_LIMIT`` (a page or a slot that is not the row's)."""
    t_verify = time.perf_counter()
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs, held = state['check']
    budget = int(ctx.traffic['output_tokens']['value'])
    lengths_ok = all(len(o) == budget for o in outputs) and held is not None
    worst, typical, per_row = float('inf'), float('inf'), []
    mean, means = float('inf'), []
    state_error, state_errors = float('inf'), []
    kv_error, kv_row_error, kv_errors = float('inf'), float('inf'), []
    if lengths_ok:
        params = _weights(ctx)
        fed = [list(p) + list(o)[:-1] for p, o in zip(prompts, outputs)]
        state_errors = [
            _state_error(params, ctx.config, tokens, rows)
            for tokens, rows in zip(fed, held[0])
        ]
        kv_errors = [
            _kv_error(params, ctx.config, tokens, k, v, _kv_rows(ctx))
            for tokens, k, v in zip(fed, *held[1:])
        ]
        state_error = float(np.max(state_errors))
        # the median over the rows of K's and of V's, and the larger
        kv_error = float(np.median(kv_errors, axis=0).max())
        kv_row_error = float(np.max(kv_errors))
        widths, scored = _reference_widths(ctx), _scored(ctx)
        for prompt, output, tokens in zip(prompts, outputs, fed):
            ids = np.zeros((1, min(w for w in widths if w >= len(tokens))), np.int32)
            ids[0, :len(tokens)] = tokens
            at = len(prompt) - 1 + scored[None]
            logits = reference.lfm2_logits(params, ctx.config, ids, at)
            gaps = reference.token_gaps(logits, [np.asarray(output)[scored]])
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
        and state_error <= reference.STATE_CONTENT_LIMIT
        and kv_error <= reference.KV_CONTENT_LIMIT
        and kv_row_error <= reference.KV_ROW_LIMIT
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
        'state_content_error': state_error,
        'state_content_error_by_row': [round(e, 5) for e in state_errors],
        'kv_content_error': kv_error,
        'kv_content_error_max_row': kv_row_error,
        'kv_content_error_by_row': [
            [round(float(e), 5) for e in row] for row in kv_errors
        ],  # [K's, V's] a row
        'check_prompt_tokens': [len(p) for p in prompts],
        'attn_backend': state['attn_backend'],
        'kv_pools': state['kv_pools'],
        'kv_walk_keys': state['kv_walk_keys'],
        'state_pool': state['state_pool'],
        # Outside set-up and window, inside the run's time limit.
        'check_s': {
            'greedy_call': round(state.get('excluded_s', 0.0), 1),
            'reference': round(time.perf_counter() - t_verify, 1),
        },
        # device seconds of kernel calls by '<program> <scope>' (traced runs)
        'kernel_call_s': state.get('kernel_call_s'),
    }


close = _engine.close
