"""Closed loop over ``LLMEngine.generate_ids`` for ``ouro`` (ByteDance Ouro
looped decoders): ``engine_closed``'s loop and window with this
architecture's own model config, seeded weights and plain reference; what
differs from the other ``*_closed`` drivers is the model, its weights, its
reference and the check's view of what a sequence holds (a K/V plane a layer
a PASS: one plane of the first pass and one of the last are read back from
the pool). The cell's file names this driver; nothing else here knows the
cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from benchmarks import reference_ouro as reference
from benchmarks.drivers import _engine, engine_closed, laguna_closed
from benchmarks.readers import lfm2 as lfm2_reader

# The rows the check scores: 8 of the greedy call's prompts, evenly spaced by
# prompt length from the shortest to the longest (``laguna_closed``'s).
CHECK_ROWS = laguna_closed.CHECK_ROWS


def _model_cfg(model: dict):
    from distllm_tpu.models import ouro

    return ouro.OuroConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import ouro

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(lambda: ouro.init_on_device(jax.random.PRNGKey(0), cfg))


def check_planes(model: dict) -> tuple[int, int]:
    """The planes the check reads back: layer 0 of the first pass (a bf16
    row of a float32 one: the pool's precision) and the last layer of the
    LAST pass, plane ``T * L - 1`` (what every pass and the final norm
    between them handed on, in a plane that only a walk over all the planes
    writes; the first layer of a pass reads its input through a norm and so
    cannot tell a normed input from a raw one)."""
    return 0, model['total_ut_steps'] * model['num_hidden_layers'] - 1


def _reference_width(ctx) -> int:
    """The check's rows are padded on the right (a causal forward never
    sees it) to ONE width, so that the reference compiles one shape."""
    spec = ctx.traffic
    return int(spec['prompt_tokens']['hi']) + int(spec['output_tokens']['value'])


def _compile_reference_ahead(ctx, split: dict) -> None:
    """On a thread beside the engine's set-up: the reference's programs
    compiled from shapes into the compile cache (``compile_ahead``), where
    ``verify`` finds them. A failure here costs ``verify`` that time again
    and nothing else."""
    t = time.perf_counter()
    try:
        reference.compile_ahead(
            ctx.config, _weight_shapes(ctx), _reference_width(ctx),
            scored=len(_scored(ctx)),
            rows=CHECK_ROWS,
        )
        split['reference_ahead'] = round(time.perf_counter() - t, 1)
    except Exception as exc:  # noqa: BLE001 -- the check compiles them itself
        split['reference_ahead'] = f'failed: {exc!r}'[:200]


def _weights(ctx):
    """The program's parameter tree (``ouro.init_on_device``'s shapes and
    types), filled on the device in one jitted call that takes the key as an
    ARGUMENT, so that every seed finds one compiled program: normal(0, 0.02)
    kernels, embedding, head and gate, unit norm scales, the gate's bias 0
    (the configuration's ``assumed`` 8)."""
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
            elif name == 'bias':
                value = jnp.zeros(leaf.shape, jnp.float32)
            else:
                value = jax.random.normal(sub, leaf.shape, jnp.float32) * 0.02
            leaves.append(value.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # the chip's own bit generator: 2.7 G threefry draws compile for 24 s
    # of a cold set-up (``solar_open2_closed._key``)
    return fill(jax.random.key(ctx.seed % (2**31), impl='rbg'))


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
        'loop': {
            key: engine.telemetry.get(key)
            for key in ('loop_window_form', 'kv_walk_keys')
        },
        'kv_pool': {
            'shape': list(engine.kv.pool_shape), 'bytes': engine.kv.hbm_bytes,
        },
        'setup_split_s': {
            'weights': round(t1 - t0, 1),
            'engine': round(time.perf_counter() - t1, 1),
        },
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.ouro  # noqa: F401 -- fail first

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


def _held(engine, model, records) -> tuple:
    """What the requests of ``records`` left behind in the check's two
    planes: the K and V of their first block (written by a prefill span)
    and of their last (written token by token in decode), ``[planes, rows,
    2, block, kv heads, d]`` each."""
    ends = np.asarray(
        [[r['kv_first_block'], r['kv_tail_block']] for r in records]
    )
    return tuple(
        np.stack([
            np.asarray(pool[plane][ends], np.float32)
            for plane in check_planes(model)
        ])
        for pool in (engine.kv.k, engine.kv.v)
    )


def _exit_counts(windows: list[dict]) -> list | None:
    """The decode records' ``loop_exit_pass`` added up: the decoded tokens
    by the pass their head read."""
    counts = [r['loop_exit_pass'] for r in windows if 'loop_exit_pass' in r]
    return np.sum(counts, axis=0).tolist() if counts else None


def check_prompts(ctx) -> list[list[int]]:
    """The check's prompts: ``CHECK_ROWS`` of a call's, evenly spaced by
    prompt length from the shortest to the longest. Fewer than the engine
    has slots, so that all are admitted at once and each keeps its blocks
    to the end (a freed block keeps what it held until its next holder
    writes it, and no holder comes)."""
    prompts = engine_closed._call_prompts(ctx, 'check')
    by_length = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    rows = sorted({
        by_length[round(j * (len(by_length) - 1) / (CHECK_ROWS - 1))]
        for j in range(CHECK_ROWS)
    })
    return [prompts[i] for i in rows]


def sample_for_check(state, ctx) -> float:
    """The greedy call of the check through the engine: ``check_prompts``
    with the cell's output budget, prefilled in chunks and decoded through
    all the planes, every row scored. Keeps the rows' prompts, tokens and
    what each left in the two planes for ``verify``. Returns the seconds it
    took (outside set-up and window)."""
    from distllm_tpu.generate.engine.engine import SamplingParams

    t = time.perf_counter()
    engine = state['engine']
    prompts = check_prompts(ctx)
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
    kept = (
        len(records) == len(prompts)
        and all('kv_first_block' in r for r in records)
        and not any(r['preemptions'] for r in records)
    )
    held = _held(engine, ctx.config, records) if kept else None
    state['check'] = (prompts, outputs, held)
    windows = [r for r in flight if r['kind'] == 'decode']
    state['check_call'] = {
        'preemptions': sum(int(r.get('preemptions', 0)) for r in records),
        'decode_rows_max': max((r['batch'] for r in windows), default=None),
        'loop_exit_pass': _exit_counts(windows),
        'decoded_tokens': sum(r['tokens'] for r in windows),
    }
    return time.perf_counter() - t


def _scored(ctx) -> np.ndarray:
    """Which of a row's generated tokens the reference scores."""
    budget = int(ctx.traffic['output_tokens']['value'])
    return np.arange(0, budget, reference.SCORE_EVERY)


def _plane_errors(pages, want, fed: int) -> float:
    """Relative RMS error of a row's first and last block of one plane
    (``pages [2, block, kv heads, d]``) against the reference's rows
    ``want [S, kv heads, d]``, over the slots the row wrote: the larger of
    the two blocks'."""
    block = pages.shape[1]
    last = (fed - 1) // block * block
    errors = []
    for which, first in ((0, 0), (1, last)):
        n = min(first + block, fed) - first
        errors.append(
            reference.content_error(pages[which, :n], want[first:first + n])
        )
    return max(errors)


def measure(state, ctx) -> dict:
    """``engine_closed``'s window, then, from ONE parse of the profiler's
    file while it is still there, the device seconds by named scope
    (``hybrid.scope_seconds``) and of the kernel calls by program and scope
    (``readers/lfm2.kernel_seconds``). A traced run's window is the traced
    call alone (``lfm2_closed.measure`` says why: stopping the profiler
    after a call takes most of a minute, inside the window, and a second
    call that no per-layer metric reads would be added to it)."""
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
    state['scope_s'] = obs['scope_s']
    records = [r for r in obs['flight'] if r.get('kind') == 'request']
    windows = [r for r in obs['flight'] if r.get('kind') == 'decode']
    state['window_engine'] = {
        'preemptions': sum(int(r.get('preemptions', 0)) for r in records),
        'decode_rows_mean': (
            sum(r['batch'] for r in windows) / len(windows) if windows else None
        ),
        'decode_rows_max': max((r['batch'] for r in windows), default=None),
        'loop_exit_pass': _exit_counts(windows),
    }
    return obs


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``_engine.verify``: the float32 reference's FULL
    forward pass at the configuration's widths (four passes of 48 layers,
    dense attention, the exit rule) scores each check row's prompt with the
    engine's own greedy tokens appended, every ``SCORE_EVERY``-th generated
    token of it, and gives the keys and values of the check's two planes.
    The limits are ``reference_ouro``'s, with their reasons in
    ``benchmarks/OURO.md``: every scored token within
    ``TOKEN_GAP_LIMIT_STD`` of the reference's largest logit and the mean
    gap within ``MEAN_GAP_LIMIT_STD``; of the first pass's plane the median
    over the rows of the larger of K's and V's error within
    ``FIRST_PASS_KV_LIMIT`` (the pool's precision), of the last pass's
    plane within ``LAST_PASS_KV_LIMIT`` (what three passes handed on), and
    every row of both within ``KV_ROW_LIMIT`` (a page that is not the
    row's, a plane never written)."""
    t_verify = time.perf_counter()
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs, held = state['check']
    budget = int(ctx.traffic['output_tokens']['value'])
    lengths_ok = all(len(o) == budget for o in outputs) and held is not None
    inf = float('inf')
    worst = mean = first_error = last_error = row_error = inf
    gaps_by_row, kv_by_row, exit_passes = [], [], None
    if lengths_ok:
        params = _weights(ctx)
        scored, planes = _scored(ctx), check_planes(ctx.config)
        fed = [list(p) + list(o)[:-1] for p, o in zip(prompts, outputs)]
        ids = np.zeros((len(fed), _reference_width(ctx)), np.int32)
        for row, tokens in enumerate(fed):
            ids[row, :len(tokens)] = tokens
        at = np.stack([len(p) - 1 + scored for p in prompts])
        want = reference.forward(params, ctx.config, ids, at, planes=planes)
        del params
        gaps = reference.token_gaps(
            want['logits'], [np.asarray(o)[scored] for o in outputs]
        )
        worst, mean = float(gaps.max()), float(gaps.mean())
        gaps_by_row = [round(float(g.max()), 4) for g in gaps]
        # [rows, planes]: the larger of K's and V's error
        kv = np.asarray([
            [
                max(
                    _plane_errors(pages[pi][row], rows[row], len(fed[row]))
                    for pages, rows in zip(held, want['planes'][plane])
                )
                for pi, plane in enumerate(planes)
            ]
            for row in range(len(fed))
        ])
        first_error, last_error = (float(e) for e in np.median(kv, axis=0))
        row_error = float(kv.max())
        kv_by_row = [[round(float(e), 5) for e in row] for row in kv]
        scored_exits = np.take_along_axis(want['exit_pass'], at, 1)
        exit_passes = np.bincount(
            scored_exits.ravel(), minlength=ctx.config['total_ut_steps']
        ).tolist()
    correct = (
        lengths_ok
        and worst <= reference.TOKEN_GAP_LIMIT_STD
        and mean <= reference.MEAN_GAP_LIMIT_STD
        and first_error <= reference.FIRST_PASS_KV_LIMIT
        and last_error <= reference.LAST_PASS_KV_LIMIT
        and row_error <= reference.KV_ROW_LIMIT
        and obs['failed'] == 0
        and (ctx.rehearsal
             or state['attn_backend'] == ctx.config['expect_attn_backend'])
    )
    return correct, {
        'token_gap_max_std': worst,
        'token_gap_mean_std': mean,
        'token_gap_max_by_row': gaps_by_row,
        'first_pass_kv_error': first_error,
        'last_pass_kv_error': last_error,
        'kv_error_max_row': row_error,
        'kv_error_by_row': kv_by_row,  # [first pass's plane, last pass's] a row
        'check_planes': list(check_planes(ctx.config)),
        'check_prompt_tokens': [len(p) for p in prompts],
        # the reference's exit pass at the scored positions, pass by pass
        'reference_exit_pass': exit_passes,
        'check_call': state.get('check_call'),
        'window_engine': state.get('window_engine'),
        'attn_backend': state['attn_backend'],
        'loop': state['loop'],
        'kv_pool': state['kv_pool'],
        # Where set-up went: weights, engine build, warm-up call, programs.
        'setup_split_s': state['setup_split_s'],
        # Outside set-up and window, inside the run's time limit.
        'check_s': {
            'greedy_call': round(state.get('excluded_s', 0.0), 1),
            'reference': round(time.perf_counter() - t_verify, 1),
        },
        # device seconds of kernel calls by '<program> <scope>' and by
        # scope (traced runs)
        'kernel_call_s': state.get('kernel_call_s'),
        'scope_s': state.get('scope_s'),
    }


close = _engine.close
