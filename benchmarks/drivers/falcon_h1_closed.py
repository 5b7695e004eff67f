"""Closed loop over ``LLMEngine.generate_ids`` for ``falcon_h1`` (tiiuae
Falcon-H1): ``engine_closed``'s loop and window with this architecture's own
model config, seeded weights and plain reference; what differs from
``granite_closed`` and ``lfm2_closed`` is the model, its weights, its
reference and the check's view of what a sequence holds (in EVERY layer a
slot of the state pool AND K and V pages). The cell's file names this
driver; nothing else here knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from benchmarks import reference_falcon_h1 as reference
from benchmarks.drivers import _engine, engine_closed, laguna_closed
from benchmarks.readers import lfm2 as lfm2_reader

# The rows the check scores: 8 of the greedy call's prompts, evenly spaced by
# prompt length from the shortest to the longest (``laguna_closed``'s).
CHECK_ROWS = laguna_closed.CHECK_ROWS
# ... and 2 prompts more, sent AFTER that call, so that each takes a slot
# whose last holder left its state there: a second holder's first span has
# to start from zeros whatever the slot holds. They run SECOND_TOKENS tokens.
SECOND_ROWS = 2
SECOND_TOKENS = 16

# How the seed fills a kind of leaf (the configuration's ``assumed`` 9): the
# standard deviation of a normal kernel by the parameter's name; ``A``
# uniform in ``_A_RANGE``, ``dt`` and ``D`` log-uniform in their ranges,
# norm scales one. The published multipliers presume muP-sized weights; at
# normal(0, 0.02) everywhere ``k * key_multiplier`` makes every score zero
# and attention a plain mean. These make, at the cut's widths, scores and
# logits of order one and the two mixers' outputs of one size.
_STD = {
    'embed': 0.177, 'head': 1.8, 'q': 0.16, 'k': 0.16, 'v': 0.014, 'o': 0.4,
    'in_proj': 0.1, 'out_proj': 0.09, 'gate': 0.08, 'up': 0.014, 'down': 0.6,
    'conv': 0.5, 'conv_bias': 0.5,
}
_A_RANGE = (1.0, 7.0)
_DT_RANGE = (0.001, 0.1)
_D_RANGE = (0.25, 4.0)


def _model_cfg(model: dict):
    from distllm_tpu.models import falcon_h1

    return falcon_h1.FalconH1Config.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import falcon_h1

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(
        lambda: falcon_h1.init_on_device(jax.random.PRNGKey(0), cfg)
    )


def _reference_widths(ctx) -> list[int]:
    """The check's rows are padded on the right (a causal forward never
    sees it) to one of three widths, so that the reference compiles three
    shapes and not one a prompt length."""
    spec = ctx.traffic
    longest = int(spec['prompt_tokens']['hi']) + int(spec['output_tokens']['value'])
    return [-(-longest // 3), -(-2 * longest // 3), longest]


def _compile_reference_ahead(ctx, split: dict) -> None:
    """On a thread beside the engine's set-up: the reference's programs
    compiled from shapes into the compile cache (``compile_ahead``), where
    ``verify`` finds them. A failure here costs ``verify`` that time again
    and nothing else."""
    t = time.perf_counter()
    try:
        reference.compile_ahead(
            ctx.config, _weight_shapes(ctx), _reference_widths(ctx),
            scored=len(_scored(ctx)),
        )
        split['reference_ahead'] = round(time.perf_counter() - t, 1)
    except Exception as exc:  # noqa: BLE001 -- the check compiles them itself
        split['reference_ahead'] = f'failed: {exc!r}'[:200]


def _weights(ctx):
    """The program's parameter tree (``falcon_h1.init_on_device``'s shapes
    and types), filled on the device in one jitted call that takes the key
    as an ARGUMENT, so that every seed finds one compiled program."""
    shapes = _weight_shapes(ctx)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    jnp = jax.numpy

    def log_uniform(key, shape, lo, hi):
        return jnp.exp(
            jax.random.uniform(key, shape, jnp.float32, np.log(lo), np.log(hi))
        )

    @jax.jit
    def fill(key):
        leaves = []
        for sub, (path, leaf) in zip(jax.random.split(key, len(paths)), paths):
            keys = [str(getattr(p, 'key', '')) for p in path]
            name = keys[-1] if keys[-1] not in ('kernel', 'scale') else keys[-2]
            if keys[-1] == 'scale':
                value = jnp.ones(leaf.shape, jnp.float32)
            elif name == 'A_log':
                value = jnp.log(jax.random.uniform(
                    sub, leaf.shape, jnp.float32, *_A_RANGE
                ))
            elif name == 'dt_bias':
                dt = log_uniform(sub, leaf.shape, *_DT_RANGE)
                value = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
            elif name == 'D':
                value = log_uniform(sub, leaf.shape, *_D_RANGE)
            else:
                value = jax.random.normal(sub, leaf.shape, jnp.float32) * _STD[name]
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
        'kv_walk_keys': engine.telemetry.get('kv_walk_keys'),
        'state_pool': engine.telemetry['state_pool'],
        'setup_split_s': {
            'weights': round(t1 - t0, 1),
            'engine': round(time.perf_counter() - t1, 1),
        },
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.falcon_h1  # noqa: F401 -- fail first

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


def _greedy(engine, prompts, budget):
    """One greedy call; its outputs and its request records in the order of
    ``prompts`` (ids are given as requests are added)."""
    from distllm_tpu.generate.engine.engine import SamplingParams

    recorded_before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=budget)
    )
    records = sorted(
        (r for r in _engine.flight_since(engine, recorded_before)
         if r['kind'] == 'request'),
        key=lambda r: r['request_id'],
    )
    return outputs, records


def _held(engine, records) -> tuple:
    """What the requests of ``records`` left behind of LAYER 0: in their
    slots of the state pool the SSM state and the convolution's rows, in
    the pool the K and V of their first block (written by a prefill span)
    and of their last (written token by token in decode)."""
    slots = np.asarray([r['state_slot'] for r in records])
    ends = np.asarray(
        [[r['kv_first_block'], r['kv_tail_block']] for r in records]
    )
    pool = engine.state_pool.state
    return (
        np.asarray(pool['ssm'][0][slots], np.float32),
        np.asarray(pool['conv'][0][slots], np.float32),
        # [rows, 2, block, kv heads, d]
        np.asarray(engine.kv.k[0][ends], np.float32),
        np.asarray(engine.kv.v[0][ends], np.float32),
    )


def sample_for_check(state, ctx) -> float:
    """The greedy calls of the check through the engine, at the cell's
    load: the call's first prompts, as many as the state pool has slots
    (each keeps its slot and its blocks to the end, and a freed slot or
    block keeps what it held until its next holder writes it), of which
    ``CHECK_ROWS`` are scored; then ``SECOND_ROWS`` of the call's next
    prompts, each of which takes a slot that still holds its last holder's
    state. Keeps the scored rows' prompts, tokens and what each left in the
    pools for ``verify``. Returns the seconds it took (outside set-up and
    window)."""
    t = time.perf_counter()
    engine = state['engine']
    every = engine_closed._call_prompts(ctx, 'check')
    slots = engine.telemetry['state_pool_slots']
    prompts = every[:slots]
    budget = int(ctx.traffic['output_tokens']['value'])
    outputs, records = _greedy(engine, prompts, budget)
    by_length = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    rows = sorted({
        by_length[round(j * (len(by_length) - 1) / (CHECK_ROWS - 1))]
        for j in range(CHECK_ROWS)
    })
    kept = (
        len(records) == len(prompts)
        and len({r['state_slot'] for r in records}) == len(records)
        and not any(r['preemptions'] for r in records)
    )
    held = _held(engine, [records[i] for i in rows]) if kept else None
    state['check'] = (
        [prompts[i] for i in rows], [outputs[i] for i in rows], held
    )
    # The second holders, after the first call's slots were read.
    second = every[slots:slots + SECOND_ROWS] or every[:SECOND_ROWS]
    outputs2, records2 = _greedy(engine, second, min(SECOND_TOKENS, budget))
    held2 = _held(engine, records2) if len(records2) == len(second) else None
    state['check_second'] = (second, outputs2, held2)
    return time.perf_counter() - t


def _scored(ctx) -> np.ndarray:
    """Which of a row's generated tokens the reference scores."""
    budget = int(ctx.traffic['output_tokens']['value'])
    return np.arange(0, budget, reference.SCORE_EVERY)


def _kv_errors(pages_k, pages_v, want_k, want_v, fed: int) -> tuple:
    """``(K's, V's)`` relative RMS error of a row's first and last block
    against the reference's rows, over the slots the row wrote, the larger
    of the two blocks' each."""
    block = pages_k.shape[1]
    last = (fed - 1) // block * block
    errors = []
    for which, first in ((0, 0), (1, last)):
        n = min(first + block, fed) - first
        errors.append([
            reference.content_error(
                pages[which, :n], want[first:first + n]
            )
            for pages, want in ((pages_k, want_k), (pages_v, want_v))
        ])
    return tuple(np.max(errors, axis=0))


def _score(params, ctx, prompts, outputs, held, scored) -> dict:
    """The reference over each row's prompt with the engine's own tokens
    appended: the token gaps at the ``scored`` generated tokens, and the
    errors of what the row left of layer 0 in the pools (it has taken in
    everything but its last token)."""
    widths = _reference_widths(ctx)
    gaps, ssm, conv, kv = [], [], [], []
    for row, (prompt, output) in enumerate(zip(prompts, outputs)):
        tokens = list(prompt) + list(output)[:-1]
        ids = np.zeros((1, min(w for w in widths if w >= len(tokens))), np.int32)
        ids[0, :len(tokens)] = tokens
        at = len(prompt) - 1 + scored[None]
        logits, want = reference.forward(
            params, ctx.config, ids, at, lengths=[len(tokens)]
        )
        gaps.append(
            reference.token_gaps(logits, [np.asarray(output)[scored]])[0]
        )
        want_ssm, want_conv, want_k, want_v = want[0]
        ssm.append(reference.content_error(held[0][row], want_ssm))
        conv.append(reference.content_error(held[1][row], want_conv))
        kv.append(_kv_errors(
            held[2][row], held[3][row], want_k, want_v, len(tokens)
        ))
    return {'gaps': np.asarray(gaps), 'ssm': ssm, 'conv': conv, 'kv': kv}


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
    return obs


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``_engine.verify``: the float32 reference at the
    configuration's widths scores each check row's prompt with the engine's
    own greedy tokens appended, one row at a time, every ``SCORE_EVERY``-th
    generated token of it, and what the row left of layer 0 in the pools.
    The limits are ``reference_falcon_h1``'s, with their reasons in
    ``benchmarks/FALCON_H1.md``: every scored token within
    ``TOKEN_GAP_LIMIT_STD`` of the reference's largest logit and the mean
    gap within ``MEAN_GAP_LIMIT_STD``, second holders included; the SSM
    state and the convolution rows in EVERY row's slot within
    ``SSM_STATE_LIMIT`` and ``CONV_STATE_LIMIT``; of layer 0's K and V
    pages the median over the rows within ``KV_CONTENT_LIMIT`` (the pool's
    precision) and every row within ``KV_ROW_LIMIT`` (a page that is not
    the row's)."""
    t_verify = time.perf_counter()
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs, held = state['check']
    second, outputs2, held2 = state['check_second']
    budget = int(ctx.traffic['output_tokens']['value'])
    lengths_ok = (
        all(len(o) == budget for o in outputs) and held is not None
        and all(len(o) == min(SECOND_TOKENS, budget) for o in outputs2)
        and held2 is not None
    )
    inf = float('inf')
    worst = mean = ssm_error = conv_error = kv_error = kv_row_error = inf
    first, late = None, None
    if lengths_ok:
        params = _weights(ctx)
        first = _score(params, ctx, prompts, outputs, held, _scored(ctx))
        late = _score(
            params, ctx, second, outputs2, held2,
            np.arange(0, len(outputs2[0]), reference.SCORE_EVERY),
        )
        del params
        gaps = np.concatenate([first['gaps'].ravel(), late['gaps'].ravel()])
        worst, mean = float(gaps.max()), float(gaps.mean())
        ssm_error = float(np.max(first['ssm'] + late['ssm']))
        conv_error = float(np.max(first['conv'] + late['conv']))
        kv = np.asarray(first['kv'] + late['kv'])  # [rows, (K's, V's)]
        kv_error = float(np.median(kv, axis=0).max())
        kv_row_error = float(kv.max())
    correct = (
        lengths_ok
        and worst <= reference.TOKEN_GAP_LIMIT_STD
        and mean <= reference.MEAN_GAP_LIMIT_STD
        and ssm_error <= reference.SSM_STATE_LIMIT
        and conv_error <= reference.CONV_STATE_LIMIT
        and kv_error <= reference.KV_CONTENT_LIMIT
        and kv_row_error <= reference.KV_ROW_LIMIT
        and obs['failed'] == 0
        and (ctx.rehearsal
             or state['attn_backend'] == ctx.config['expect_attn_backend'])
    )

    def by_row(name, digits=5):
        if first is None:
            return []
        return [
            [round(float(e), digits) for e in np.atleast_1d(row)]
            for row in first[name] + late[name]
        ]  # the check's rows, then the second holders

    return correct, {
        'token_gap_max_std': worst,
        'token_gap_mean_std': mean,
        'token_gap_max_by_row': [
            round(float(g.max()), 4)
            for part in (first, late) if part for g in part['gaps']
        ],
        'ssm_state_error': ssm_error,
        'ssm_state_error_by_row': by_row('ssm'),
        'conv_state_error': conv_error,
        'conv_state_error_by_row': by_row('conv'),
        'kv_content_error': kv_error,
        'kv_content_error_max_row': kv_row_error,
        'kv_content_error_by_row': by_row('kv'),  # [K's, V's] a row
        'check_prompt_tokens': [len(p) for p in prompts + second],
        'attn_backend': state['attn_backend'],
        'kv_pools': state['kv_pools'],
        'kv_walk_keys': state['kv_walk_keys'],
        'state_pool': state['state_pool'],
        # Where set-up went: weights, engine build, warm-up call, programs.
        'setup_split_s': state['setup_split_s'],
        # Outside set-up and window, inside the run's time limit.
        'check_s': {
            'greedy_calls': round(state.get('excluded_s', 0.0), 1),
            'reference': round(time.perf_counter() - t_verify, 1),
        },
        # device seconds of kernel calls by '<program> <scope>' and by
        # scope (traced runs)
        'kernel_call_s': state.get('kernel_call_s'),
        'scope_s': state.get('scope_s'),
    }


close = _engine.close
