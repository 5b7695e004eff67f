"""Closed loop over ``LLMEngine.generate_ids`` for ``granitemoehybrid``
(Granite 4.0-H): ``engine_closed``'s loop and window with this
architecture's own model config, seeded weights and plain reference. The
cell's file names this driver; nothing else here knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import reference, reference_granite
from benchmarks.drivers import _engine, engine_closed

# A leaf's last path key -> how the seed fills it (everything else:
# normal(0, 0.02)); the configuration's file lists these under ``assumed``.
_ONES = ('scale', 'D')
_SCALES = {'conv': 0.5, 'conv_bias': 0.5, 'embed': 0.02 / 12}

# The check runs at the cell's load: one greedy call of a call's first
# prompts, as many as the state pool has slots (so each keeps its slot to the
# end and the pool still holds its last state afterwards), with the cell's
# output budget. ``CHECK_ROWS`` of them, evenly spaced by prompt length from
# the shortest to the longest (so some are carried across prefill chunks),
# are scored by the reference: the SSM state each left in the pool, and every
# ``CHECK_TOKEN_STRIDE``-th of its tokens (the positions the token-gap limit
# was calibrated on: 8 rows x 16, see ``reference_granite``).
CHECK_ROWS = 8
CHECK_TOKEN_STRIDE = 8


def _model_cfg(model: dict):
    from distllm_tpu.models import granite_hybrid

    return granite_hybrid.GraniteHybridConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weights(ctx):
    """The program's parameter tree (``granite_hybrid.init_on_device``'s
    shapes and types), filled on the device in one jitted call that takes
    the key as an ARGUMENT, so that every seed finds one compiled program."""
    from distllm_tpu.models import granite_hybrid

    cfg = _model_cfg(ctx.config)
    shapes = jax.eval_shape(
        lambda: granite_hybrid.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    jnp = jax.numpy

    @jax.jit
    def fill(key):
        leaves = []
        for sub, (path, leaf) in zip(jax.random.split(key, len(paths)), paths):
            name = str(getattr(path[-1], 'key', ''))  # 'embed', 'kernel', 'conv', ...
            if name in _ONES:
                value = jnp.ones(leaf.shape, jnp.float32)
            elif name == 'A_log':
                value = jnp.log(jax.random.uniform(
                    sub, leaf.shape, jnp.float32, 1.0, 16.0
                ))
            elif name == 'dt_bias':
                dt = jnp.exp(jax.random.uniform(
                    sub, leaf.shape, jnp.float32, np.log(0.001), np.log(0.1)
                ))
                value = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
            else:
                value = jax.random.normal(
                    sub, leaf.shape, jnp.float32
                ) * _SCALES.get(name, 0.02)
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
        'state_pool_bytes': engine.telemetry['state_pool_bytes'],
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.granite_hybrid  # noqa: F401 -- fail first

    state = build(ctx)
    engine = state['engine']
    budget = int(ctx.traffic['output_tokens']['value'])
    for n in range(int(ctx.workload.get('warmup', {}).get('replica_calls', 1))):
        engine.generate_ids(
            engine_closed._call_prompts(ctx, f'warmup{n}'),
            _engine.sampling(ctx, budget),
        )
    state['excluded_s'] = sample_for_check(state, ctx)
    return state


def sample_for_check(state, ctx) -> float:
    """The greedy call of the check through the engine; keeps, for
    ``verify``, the scored rows' prompts, tokens and the SSM states they
    left in the state pool. Returns the seconds it took (outside set-up and
    window)."""
    from distllm_tpu.generate.engine.engine import SamplingParams

    t = time.perf_counter()
    engine = state['engine']
    prompts = engine_closed._call_prompts(ctx, 'check')
    prompts = prompts[: engine.telemetry['state_pool_slots']]
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
    slots = [r['state_slot'] for r in records]
    # A slot keeps its last holder's state until its next holder's first
    # prefill span, so the pool is read only where every request of the call
    # kept one slot of its own from admission to its finish.
    state_kept = (
        len(records) == len(prompts) and len(set(slots)) == len(slots)
        and not any(r['preemptions'] for r in records)
    )
    by_length = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    rows = sorted({
        by_length[round(j * (len(by_length) - 1) / (CHECK_ROWS - 1))]
        for j in range(CHECK_ROWS)
    })
    ssm = None
    if state_kept:
        at = np.asarray([slots[i] for i in rows])
        ssm = [np.asarray(leaf[at]) for leaf in engine.state_pool.state['ssm']]
    state['check'] = (
        [prompts[i] for i in rows], [outputs[i] for i in rows], ssm
    )
    return time.perf_counter() - t


def measure(state, ctx) -> dict:
    """``engine_closed``'s window, then the device seconds by named scope
    of the traced slice, read while the profiler's files are still there."""
    from benchmarks.readers import hybrid

    obs = engine_closed.measure(state, ctx)
    obs['scope_s'] = hybrid.collect_scope_seconds(ctx.capture)
    return obs


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``_engine.verify``: the float32 reference at the
    configuration's widths scores each check row's prompt with the engine's
    own greedy tokens appended (prefill, then decode through the state
    pool). Three limits, all of ``reference_granite`` with their reasons:
    every scored token within ``TOKEN_GAP_LIMIT_STD`` of the reference's
    largest logit; the SSM state each row left in the pool (it has taken in
    everything but the row's last token) within ``SSM_STATE_LIMIT`` of the
    reference's in every Mamba layer; and within ``SSM_SLOW_HEADS_LIMIT``
    over the slowest heads of the first, which a state kept in bfloat16
    fails."""
    close(state)  # frees the engine's HBM for the reference's weights
    prompts, outputs, ssm = state['check']
    budget = int(ctx.traffic['output_tokens']['value'])
    lengths_ok = all(len(o) == budget for o in outputs)
    params = _weights(ctx)
    width = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    ids = np.zeros((len(prompts), width), np.int32)
    for row, (p, o) in enumerate(zip(prompts, outputs)):
        ids[row, : len(p) + len(o)] = list(p) + list(o)
    fed = [len(p) + len(o) - 1 for p, o in zip(prompts, outputs)]
    logits, want_ssm = reference_granite.granite_forward(
        params, ctx.config, ids, fed
    )
    gaps = np.asarray(
        reference.token_gaps(logits, [len(p) for p in prompts], outputs)
    ).reshape(len(prompts), -1)[:, CHECK_TOKEN_STRIDE - 1::CHECK_TOKEN_STRIDE]
    state_errors, slow_heads_error = [], None  # no state to read
    if ssm is not None and lengths_ok:
        state_errors = reference_granite.state_errors(ssm, want_ssm)
        slow_heads_error = reference_granite.slow_head_state_error(
            ssm[0], want_ssm[0], params['mamba']['dt_bias'][0],
            params['mamba']['A_log'][0],
        )
    del params, logits, want_ssm
    worst = float(gaps.max()) if gaps.size else float('inf')
    worst_state = max(state_errors, default=None)
    correct = (
        lengths_ok
        and worst <= reference_granite.TOKEN_GAP_LIMIT_STD
        and slow_heads_error is not None
        and worst_state <= reference_granite.SSM_STATE_LIMIT
        and slow_heads_error <= reference_granite.SSM_SLOW_HEADS_LIMIT
        and obs['failed'] == 0
        and (ctx.rehearsal
             or state['attn_backend'] == ctx.config['expect_attn_backend'])
    )
    return correct, {
        'token_gap_max_std': worst,
        'token_gap_positions': int(gaps.size),
        'ssm_state_error_max': worst_state,
        'ssm_state_errors': [round(e, 6) for e in state_errors],
        'ssm_slow_heads_error': slow_heads_error,
        'attn_backend': state['attn_backend'],
        'state_pool_bytes': state['state_pool_bytes'],
    }


close = _engine.close
