"""Closed loop over ``LLMEngine.generate_ids`` for ``solar_open2`` (upstage
Solar-Open2): ``engine_closed``'s loop and ``falcon_h1_closed``'s traced
window with this architecture's own model config, seeded weights and plain
reference; what differs from the other hybrid drivers is the model, its
weights, its reference and the check's view of what a sequence holds (a
float32 matrix state and three convolutions' rows in every KDA layer's slot,
K and V pages of the one attention layer), and one comparison the others do
not have: the two forms of the program's recurrence against the reference's
from EQUAL operands (``_recurrence_forms``), where no routed expert and no
bf16 projection stands between a precision and its reading. The cell's file
names this driver; nothing else here knows the cell.

The first act of ``prepare`` is the import of the program's model module, so
that a checkout without it fails at once, before anything is allocated.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference_solar_open2 as reference
from benchmarks.drivers import _engine, engine_closed, falcon_h1_closed, laguna_closed

# The rows the check scores: 8 of the greedy call's prompts, evenly spaced by
# prompt length from the shortest to the longest (``laguna_closed``'s): the
# 16,384-token prompt is always among them.
CHECK_ROWS = laguna_closed.CHECK_ROWS
# ... and 2 prompts more, sent AFTER that call, so that each takes a slot
# whose last holder left its state there: a second holder's first span has
# to start from zeros whatever the slot holds. They run SECOND_TOKENS tokens.
SECOND_ROWS = falcon_h1_closed.SECOND_ROWS
SECOND_TOKENS = falcon_h1_closed.SECOND_TOKENS
# The check's rows are padded on the right (a causal forward never sees it)
# to the next of these shares of the longest row, so that the reference
# compiles four shapes and not one a prompt length.
_WIDTH_SHARES = (8, 4, 2, 1)

def _model_cfg(model: dict):
    from distllm_tpu.models import solar_open2

    return solar_open2.SolarOpen2Config.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )


def _weight_shapes(ctx):
    from distllm_tpu.models import solar_open2

    cfg = _model_cfg(ctx.config)
    return jax.eval_shape(
        lambda: solar_open2.init_on_device(jax.random.PRNGKey(0), cfg)
    )


def _reference_widths(ctx) -> list[int]:
    spec = ctx.traffic
    longest = int(spec['prompt_tokens']['hi']) + int(spec['output_tokens']['value'])
    return [-(-longest // share) for share in _WIDTH_SHARES]


def _compile_reference_ahead(ctx, split: dict) -> None:
    """On a thread beside the engine's set-up: the reference's programs
    compiled from shapes into the compile cache (``compile_ahead``), where
    ``verify`` finds them. A failure here costs ``verify`` that time again
    and nothing else."""
    t = time.perf_counter()
    try:
        shapes = _weight_shapes(ctx)
        reference.compile_ahead(
            ctx.config, shapes, _reference_widths(ctx),
            scored=len(_scored(ctx)), operands=jnp.dtype(ctx.config['dtype']),
        )
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        x = jax.ShapeDtypeStruct(
            (_reference_widths(ctx)[-1], shapes['embed'].shape[1]), jnp.float32
        )
        _recurrence_forms(ctx).lower(x, shapes['kda'], i32, i32, i32).compile()
        split['reference_ahead'] = round(time.perf_counter() - t, 1)
    except Exception as exc:  # noqa: BLE001 -- the check compiles them itself
        split['reference_ahead'] = f'failed: {exc!r}'[:200]


def _key(ctx):
    """The seed as a key of the ``rbg`` generator: ``fold_in`` and the
    draws are the chip's own bit generator, which compiles in seconds where
    3.3 G threefry draws took half a minute of a cold set-up."""
    return jax.random.key(ctx.seed % (2**31), impl='rbg')


def _weights(ctx):
    """The program's own seeded weights (``solar_open2.init_on_device``:
    the configuration's ``assumed`` 9), one jitted call that takes the key
    as an ARGUMENT, so that every seed finds one compiled program."""
    from distllm_tpu.models import solar_open2

    return solar_open2.init_on_device(_key(ctx), _model_cfg(ctx.config))


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
        'moe_form': engine.telemetry.get('moe_form'),
        'moe_grouped_tiles': engine.telemetry.get('moe_grouped_tiles'),
        'setup_split_s': {
            'weights': round(t1 - t0, 1),
            'engine': round(time.perf_counter() - t1, 1),
        },
    }


def prepare(ctx) -> dict:
    import distllm_tpu.models.solar_open2  # noqa: F401 -- fail first

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


def _held(engine, records) -> tuple:
    """What the requests of ``records`` left behind: in their slots of the
    state pool EVERY KDA layer's matrix state and convolution rows (``[rows,
    layers, ...]``), in the pool the K and V of the attention layer's first
    block (written by a prefill span) and last (written token by token in
    decode)."""
    slots = np.asarray([r['state_slot'] for r in records])
    ends = np.asarray(
        [[r['kv_first_block'], r['kv_tail_block']] for r in records]
    )
    pool = engine.state_pool.state
    return (
        np.stack([np.asarray(p[slots], np.float32) for p in pool['kda']], 1),
        np.stack([np.asarray(p[slots], np.float32) for p in pool['conv']], 1),
        # [rows, 2, block, kv heads, d]
        np.asarray(engine.kv.k[0][ends], np.float32),
        np.asarray(engine.kv.v[0][ends], np.float32),
    )


def _recurrence_forms(ctx):
    """The two forms of the program's recurrence, driven as the engine
    drives one row, from operands the reference's recurrence can be given
    too: ``run(x [W, hidden] float32, the KDA tree, layer, prompt tokens,
    tokens fed) -> (state [H, d_k, d_v], (k, v, g, beta))``. ``x`` is a
    KDA layer's input as the REFERENCE computed it; the program's own
    ``solar_open2._kda_inputs`` makes the operands of it (cast to the model's
    dtype, normed, projected, convolved: ``k, v`` in the model's dtype, ``g,
    beta`` float32, both 0 where a position does not count); the prompt goes
    through ``kda.kda_span`` in the engine's spans of
    ``prefill_chunk_tokens`` (state and convolution rows carried from span
    to span, the last one ragged: the walk of ``kda_mixer_span``), the
    generated tokens one at a time through ``kda.kda_step``. The reference's
    token-by-token recurrence over the SAME operands
    (``reference.recurrence_state``) is what the state is held against: what
    separates the two is the forms' arithmetic alone. Both forms are looked
    up when this is traced: a wrong one patched into ``ops.kda`` is the one
    that runs."""
    from distllm_tpu.models import common, solar_open2
    from distllm_tpu.ops import kda

    cfg = _model_cfg(ctx.config)
    span = int(ctx.config['engine']['prefill_chunk_tokens'])
    steps = int(ctx.traffic['output_tokens']['value']) - 1
    spec = cfg.state_spec()

    def run(x, tree, xi, n_prompt, n_fed):
        lp = common.layer_at(tree, xi)
        u = solar_open2._norm(x.astype(cfg.dtype), lp['ln']['scale'], cfg)
        rounds = -(-u.shape[0] // span)
        u = jnp.pad(u, ((0, rounds * span + steps - u.shape[0]), (0, 0)))

        def one_span(carry, xs):  # ``kda_mixer_span``'s walk, one row
            state, conv = carry
            first, u_span = xs
            tails = jnp.clip(n_prompt - first, 0, span)[None]
            counts = jnp.arange(span)[None] < tails[:, None]
            q, k, v, g, beta, window = solar_open2._kda_inputs(
                u_span[None], lp, cfg, conv
            )
            g = jnp.where(counts[..., None, None], g, 0.0)
            beta = jnp.where(counts[..., None], beta, 0.0)
            _, state = kda.kda_span(q, k, v, g, beta, state)
            conv = common.conv_tail(window, tails, conv.shape[1])
            return (state, conv.astype(conv0.dtype)), (k[0], v[0], g[0], beta[0])

        conv0 = jnp.zeros((1, *spec['conv'][0].shape), spec['conv'][0].dtype)
        (state, conv), prompt = jax.lax.scan(
            one_span,
            (jnp.zeros((1, *spec['kda'][0].shape), jnp.float32), conv0),
            (jnp.arange(rounds) * span,
             u[:rounds * span].reshape(rounds, span, -1)),
        )
        # the generated tokens, one at a time (``kda_mixer_step``'s walk)
        fed = jax.lax.dynamic_slice_in_dim(u, n_prompt, steps, 0)
        q, k, v, g, beta, _ = (
            t[0] for t in solar_open2._kda_inputs(fed[None], lp, cfg, conv)
        )
        live = n_prompt + jnp.arange(steps) < n_fed
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)

        def one_step(state, xs):
            _, state = kda.kda_step(*(t[None] for t in xs), state)
            return state, None

        state, _ = jax.lax.scan(one_step, state, (q, k, v, g, beta))
        # one sequence of operands: the prompt's, then the generated tokens'
        operands = tuple(
            jax.lax.dynamic_update_slice_in_dim(
                jnp.pad(
                    p.reshape(rounds * span, *p.shape[2:]),
                    ((0, steps),) + ((0, 0),) * (p.ndim - 2),
                ), t, n_prompt, 0,
            )
            for p, t in zip(prompt, (k, v, g, beta))
        )
        return state[0], operands

    return jax.jit(run)


def sample_for_check(state, ctx) -> float:
    """The greedy calls of the check through the engine, at the cell's
    load: the call's first prompts, as many as the state pool has slots
    (each keeps its slot and its blocks to the end, and a freed slot or
    block keeps what it held until its next holder writes it), of which
    ``CHECK_ROWS`` are scored; then ``SECOND_ROWS`` prompts more, each of
    which takes a slot that still holds its last holder's state. Keeps the
    scored rows' prompts, tokens and what each left in the pools for
    ``verify``. Returns the seconds it took (outside set-up and window)."""
    t = time.perf_counter()
    engine = state['engine']
    every = engine_closed._call_prompts(ctx, 'check')
    slots = engine.telemetry['state_pool_slots']
    prompts = every[:slots]
    budget = int(ctx.traffic['output_tokens']['value'])
    outputs, records = falcon_h1_closed._greedy(engine, prompts, budget)
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
    # The second holders, after the first call's slots were read: the
    # shortest prompts there are, so that the reference's rows stay short.
    rest = every[slots:] or every
    second = sorted(rest, key=len)[:SECOND_ROWS]
    outputs2, records2 = falcon_h1_closed._greedy(
        engine, second, min(SECOND_TOKENS, budget)
    )
    held2 = _held(engine, records2) if len(records2) == len(second) else None
    state['check_second'] = (second, outputs2, held2)
    return time.perf_counter() - t


def _scored(ctx) -> np.ndarray:
    """Which of a row's generated tokens the reference scores."""
    budget = int(ctx.traffic['output_tokens']['value'])
    return np.arange(0, budget, reference.SCORE_EVERY)


def _score(params, ctx, prompts, outputs, held, scored, forms) -> dict:
    """The reference over each row's prompt with the engine's own tokens
    appended: the token gaps at the ``scored`` generated tokens, the errors
    of what the row left in the pools (it has taken in everything but its
    last token), every KDA layer's, and for every KDA layer the error of
    the program's recurrence ``forms`` against the reference's recurrence
    from equal operands (``_recurrence_forms``)."""
    widths = _reference_widths(ctx)
    gaps, matrix, exact, conv, kv, equal = [], [], [], [], [], []
    for row, (prompt, output) in enumerate(zip(prompts, outputs)):
        tokens = list(prompt) + list(output)[:-1]
        width = min(w for w in widths if w >= len(tokens))
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(tokens)] = tokens
        at = len(prompt) - 1 + scored[None]
        from_equal_operands = []

        def probe(_, kind, xi, x):
            if kind != 'kda':
                return
            x = jnp.pad(x, ((0, widths[-1] - x.shape[0]), (0, 0)))
            state, operands = forms(
                x, params['kda'], jnp.int32(xi), jnp.int32(len(prompt)),
                jnp.int32(len(tokens)),
            )
            want = reference.recurrence_state(
                ctx.config, *(t[:width] for t in operands)
            )
            from_equal_operands.append(reference.content_error(state, want))

        logits, want = reference.forward(
            params, ctx.config, ids, at, lengths=[len(tokens)], probe=probe
        )
        gaps.append(
            reference.token_gaps(logits, [np.asarray(output)[scored]])[0]
        )
        states = want[0]['kda']  # [(state, conv rows)] a KDA layer
        matrix.append([
            reference.content_error(held[0][row, xi], states[xi][0])
            for xi in range(len(states))
        ])
        exact.append(reference.bf16_share(held[0][row]))
        conv.append([
            reference.content_error(held[1][row, xi], states[xi][1])
            for xi in range(len(states))
        ])
        equal.append(from_equal_operands)
        want_k, want_v = want[0]['gqa'][0]
        kv.append(falcon_h1_closed._kv_errors(
            held[2][row], held[3][row], want_k, want_v, len(tokens)
        ))
    return {
        'gaps': np.asarray(gaps), 'matrix': matrix, 'exact': exact,
        'conv': conv, 'kv': kv, 'equal': equal,
    }


measure = falcon_h1_closed.measure


def verify(state, ctx, obs) -> tuple[bool, dict]:
    """Teacher-forced, as ``falcon_h1_closed.verify``: the float32
    reference at the configuration's widths scores each check row's prompt
    with the engine's own greedy tokens appended, one row at a time, every
    ``SCORE_EVERY``-th generated token of it, and what the row left in the
    pools. The limits are ``reference_solar_open2``'s, with their reasons
    in ``benchmarks/SOLAR_OPEN2.md``: every scored token within
    ``TOKEN_GAP_LIMIT_STD`` of the reference's largest logit and the mean
    gap within ``MEAN_GAP_LIMIT_STD``, second holders included; EVERY KDA
    layer's matrix state in every row's slot within ``KDA_STATE_LIMIT`` plus
    ``KDA_STATE_LIMIT_A_LAYER`` for each routed layer before it (bf16 turns
    over a token's 8th choice there, so this limit holds the equations and
    the slots, not a precision) and stored as float32
    (``KDA_STATE_BF16_SHARE_LIMIT``); the two forms of the program's
    recurrence within ``KDA_EQUAL_OPERAND_LIMIT`` of the reference's
    recurrence from EQUAL operands (``_recurrence_forms``) in every layer
    and row: the limit that holds the recurrence to float32; the
    convolution rows' median over the rows within ``CONV_STATE_LIMIT`` a
    routed layer before them and each within ``CONV_ROW_LIMIT``; of the
    attention layer's K and V pages the median over the rows within
    ``KV_CONTENT_LIMIT`` (the pool's precision) and every row within
    ``KV_ROW_LIMIT`` (a page that is not the row's)."""
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
    # the routed layers before each KDA layer: every layer before it
    before = np.asarray([
        li for li, (kind, _) in enumerate(reference.layer_kinds(ctx.config))
        if kind == 'kda'
    ])
    matrix_limit = (
        reference.KDA_STATE_LIMIT + reference.KDA_STATE_LIMIT_A_LAYER * before
    )
    conv_limit = reference.CONV_STATE_LIMIT * before
    inf = float('inf')
    worst = mean = kv_error = kv_row_error = conv_row_error = equal_error = inf
    matrix_error = conv_error = np.full(before.shape, inf)
    bf16_share = 1.0
    first, late = None, None
    if lengths_ok:
        params = _weights(ctx)
        forms = _recurrence_forms(ctx)
        first = _score(params, ctx, prompts, outputs, held, _scored(ctx), forms)
        late = _score(
            params, ctx, second, outputs2, held2,
            np.arange(0, len(outputs2[0]), reference.SCORE_EVERY), forms,
        )
        del params
        gaps = np.concatenate([first['gaps'].ravel(), late['gaps'].ravel()])
        worst, mean = float(gaps.max()), float(gaps.mean())
        # [rows, KDA layers]: the largest row of each layer
        matrix_error = np.max(first['matrix'] + late['matrix'], axis=0)
        bf16_share = float(np.max(first['exact'] + late['exact']))
        equal_error = float(np.max(first['equal'] + late['equal']))
        # three positions a row: one token whose 8th expert bf16 turned over
        # reads 6% there, so a layer's median over the rows and the largest
        # row apart
        conv_error = np.median(first['conv'] + late['conv'], axis=0)
        conv_row_error = float(np.max(first['conv'] + late['conv']))
        kv = np.asarray(first['kv'] + late['kv'])  # [rows, (K's, V's)]
        kv_error = float(np.median(kv, axis=0).max())
        kv_row_error = float(kv.max())
    correct = bool(
        lengths_ok
        and worst <= reference.TOKEN_GAP_LIMIT_STD
        and mean <= reference.MEAN_GAP_LIMIT_STD
        and (matrix_error <= matrix_limit).all()
        and bf16_share <= reference.KDA_STATE_BF16_SHARE_LIMIT
        and equal_error <= reference.KDA_EQUAL_OPERAND_LIMIT
        and (conv_error <= conv_limit).all()
        and conv_row_error <= reference.CONV_ROW_LIMIT
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
        # a KDA layer each: the largest row, and the limit at its depth
        'kda_state_error': [float(e) for e in matrix_error],
        'kda_state_limit': [round(float(x), 4) for x in matrix_limit],
        'kda_state_error_by_row': by_row('matrix'),
        'kda_state_bf16_share': bf16_share,
        'kda_equal_operand_error': equal_error,
        'kda_equal_operand_error_by_row': by_row('equal', 7),
        # a KDA layer each: the median over the rows
        'conv_state_error': [float(e) for e in conv_error],
        'conv_state_limit': [round(float(x), 4) for x in conv_limit],
        'conv_state_error_max_row': conv_row_error,
        'conv_state_error_by_row': by_row('conv'),
        'kv_content_error': kv_error,
        'kv_content_error_max_row': kv_row_error,
        'kv_content_error_by_row': by_row('kv'),  # [K's, V's] a row
        'check_prompt_tokens': [len(p) for p in prompts + second],
        'attn_backend': state['attn_backend'],
        'kv_pools': state['kv_pools'],
        'kv_walk_keys': state['kv_walk_keys'],
        'state_pool': state['state_pool'],
        # the form of the routed experts' matmuls by program rows, and the
        # grouped kernel's tiles
        'moe_form': state['moe_form'],
        'moe_grouped_tiles': state['moe_grouped_tiles'],
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
