"""The plain reference for ``solar_open2`` (upstage Solar-Open2): the forward
pass of ISSUE 45's equations in straightforward ``jax.numpy``, weights as
stored, everything else float32 under
``jax.default_matmul_precision('highest')``. No cache, no state carried, no
kernels, no batching, no chunk form: one row at a time, the Kimi-delta layer
as the token-by-token recurrence (a ``lax.scan`` over positions), the gated
attention layer as a masked softmax over the whole row. It shares no code with
``distllm_tpu/models/`` or ``distllm_tpu/ops/``; the parameter tree's key
names (``solar_open2.init_on_device``'s) and the configuration file's keys
are all it takes from the program. It is given the chip's expert share (the
held experts' banks, ids ``first_local_expert`` onward) and its slice of the
vocabulary, and computes what the chip computes: nothing stands in for the
absent experts.

Computed in blocks so that it fits beside nothing but the bf16 weights at the
published widths: a layer's mixer weights are cast up one layer at a time,
attention goes by ``QUERY_BLOCK`` queries against the whole row, the routed
experts one held expert at a time, the head at the scored positions alone.

For a layer on ``x [S, hidden]`` (ASSUMED n: the configuration file's
``assumed`` item n)::

    u = rms(x; input norm)
    KDA (layers not in gqa_layers):
        q~, k~, v~ = u W_q, u W_k, u W_v        each a causal depthwise
        convolution of 4 taps, then SiLU        (tap j times u_{t - 3 + j})
        q = l2norm_head(q') / sqrt(d_k); k = l2norm_head(k')
        g_t = -exp(A_log_h) softplus(u W_f1 W_f2 + dt_bias)   [H, d_k]
        b_t = 2 sigmoid(u W_b)                                [H]
        S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
        o_t = S_t^T q_t
        m = (rms_head(o_t; w) * sigmoid(u W_g1 W_g2 + c)) W_o
    gated GQA (layers in gqa_layers): no positional encoding, causal softmax
        at 1 / sqrt(head_dim); m = (attn * sigmoid(u W_gate)) W_o
    x = x + m;  n = rms(x; post norm)
    s = sigmoid(n W_r) over all routed experts; the k largest of s + bias
    chosen; weights s_e / sum of the chosen s, times routed_scaling_factor
    x = x + sum over the chosen HELD experts of w_e swiglu_e(n) + shared(n)
    logits = rms(x; final norm) W_head
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256  # queries of the attention layer a block
L2_EPS = 1e-6  # under the root of a head's squared norm (ASSUMED 3)

_KDA = (
    'ln', 'q', 'k', 'v', 'conv', 'f_a', 'f_b', 'A_log', 'dt_bias', 'b', 'g_a',
    'g_b', 'g_bias', 'o_norm', 'o',
)
_GQA = ('ln', 'q', 'k', 'v', 'attn_gate', 'o')
_MOE_SMALL = (
    'mlp_ln', 'router', 'router_bias', 'shared_gate', 'shared_up',
    'shared_down',
)
_BANKS = ('gate', 'up', 'down')


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _leaf(tree, name):
    """The array of a wrapped leaf (``{'kernel'}``, ``{'scale'}``,
    ``{'taps'}``, ``{'bias'}``) or the bare leaf."""
    leaf = tree[name]
    return next(iter(leaf.values())) if isinstance(leaf, dict) else leaf


def _numbers(model: dict) -> tuple:
    """The model's numbers as a hashable tuple (a jitted program a model)."""
    linear = model['linear_attn_config']
    return (
        model['num_attention_heads'], model['num_key_value_heads'],
        model['head_dim'], linear['num_heads'], linear['head_dim'],
        linear['short_conv_kernel_size'], model['rms_norm_eps'],
        model['num_experts_per_tok'], float(model['routed_scaling_factor']),
        model.get('first_local_expert', 0),
        2.0 if model['kda_allow_neg_eigval'] else 1.0,
    )


def layer_kinds(model: dict) -> list[tuple[str, int]]:
    """``(kind, index in the kind's tree)`` of every layer."""
    out, seen = [], {'kda': 0, 'gqa': 0}
    for li in range(model['num_hidden_layers']):
        kind = 'gqa' if li in model['gqa_layers'] else 'kda'
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


@functools.lru_cache(maxsize=None)
def _programs(numbers: tuple):
    (heads, kv_heads, d, k_heads, k_d, taps_n, eps, top_k, routed_scale,
     first_expert, beta_scale) = numbers
    wide = k_heads * k_d

    def cut(stacks, names, li):
        return {
            n: jax.lax.dynamic_index_in_dim(
                _leaf(stacks, n), li, 0, keepdims=False
            ).astype(F32)
            for n in names
        }

    def delta_rule(state, k_t, v_t, g_t, b_t):
        """One position of the recurrence, as written: ``S_t`` from ``S_{t-1}
        [H, d_k, d_v]``, ``k_t, g_t [H, d_k]``, ``v_t [H, d_v]``, ``b_t
        [H]``."""
        state = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.einsum('hk,hkv->hv', k_t, state)
        return state + (
            b_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None, :]
        )

    def recurrence(k, v, g, beta):
        """The recurrence alone over operands GIVEN (``k, g [S, H, d_k]``,
        ``v [S, H, d_v]``, ``beta [S, H]``, any float dtype, taken up to
        float32) from a zero state: the state after the last position. A
        position with ``g = 0`` and ``beta = 0`` leaves the state as it
        is."""
        with jax.default_matmul_precision('highest'):
            zeros = jnp.zeros((k_heads, k_d, v.shape[-1]), F32)
            state, _ = jax.lax.scan(
                lambda state, xs: (delta_rule(state, *xs), None), zeros,
                tuple(t.astype(F32) for t in (k, v, g, beta)),
            )
            return state

    def kda(x, stacks, li, length):
        """``x + m`` of KDA layer ``li`` of its tree for one row ``x [S,
        hidden]``, and what a sequence holds of the layer after ``length``
        tokens: the state ``[H, d_k, d_v]`` and the convolutions' last ``K
        - 1`` inputs ``[K - 1, 3 * H * d_k]`` (q's, k's, v's)."""
        with jax.default_matmul_precision('highest'):
            lp = cut(stacks, _KDA, li)
            s = x.shape[0]
            u = _rms(x, lp['ln'], eps)
            taps = jnp.split(lp['conv'], 3, axis=-1)  # q's, k's, v's

            def convolved(name, taps):
                """One projection through its own convolution and SiLU, and
                the last ``K - 1`` inputs it has seen after ``length``."""
                padded = jnp.pad(u @ lp[name], ((taps_n - 1, 0), (0, 0)))
                rows = jax.lax.dynamic_slice_in_dim(
                    padded, length, taps_n - 1, 0
                )
                conv = sum(taps[j] * padded[j:j + s] for j in range(taps_n))
                return jax.nn.silu(conv).reshape(s, k_heads, k_d), rows

            (q, q_rows), (k, k_rows), (v, v_rows) = (
                convolved(name, t) for name, t in zip('qkv', taps)
            )
            conv_rows = jnp.concatenate([q_rows, k_rows, v_rows], axis=-1)
            q, k = _l2(q) / math.sqrt(k_d), _l2(k)
            decay = jax.nn.softplus((u @ lp['f_a']) @ lp['f_b'] + lp['dt_bias'])
            g = -jnp.exp(lp['A_log'])[:, None] * decay.reshape(s, k_heads, k_d)
            beta = beta_scale * jax.nn.sigmoid(u @ lp['b'])  # [S, H]

            def step(carry, xs):
                state, kept = carry
                t, q_t, *operands = xs
                state = delta_rule(state, *operands)
                kept = jnp.where(t < length, state, kept)
                return (state, kept), jnp.einsum('hk,hkv->hv', q_t, state)

            zeros = jnp.zeros((k_heads, k_d, k_d), F32)
            (_, kept), o = jax.lax.scan(
                step, (zeros, zeros), (jnp.arange(s), q, k, v, g, beta)
            )
            gate = jax.nn.sigmoid((u @ lp['g_a']) @ lp['g_b'] + lp['g_bias'])
            o = _rms(o, lp['o_norm'], eps) * gate.reshape(s, k_heads, k_d)
            return x + o.reshape(s, wide) @ lp['o'], (kept, conv_rows)

    def gqa(x, stacks, li):
        """``x + m`` of gated attention layer ``li`` of its tree, and the
        row's ``k, v [S, kv_heads, d]`` as a page holds them."""
        with jax.default_matmul_precision('highest'):
            lp = cut(stacks, _GQA, li)
            s = x.shape[0]
            u = _rms(x, lp['ln'], eps)
            q = (u @ lp['q']).reshape(s, heads, d)
            k = (u @ lp['k']).reshape(s, kv_heads, d)
            v = (u @ lp['v']).reshape(s, kv_heads, d)
            group = heads // kv_heads
            k_all, v_all = (jnp.repeat(t, group, axis=1) for t in (k, v))
            block = min(QUERY_BLOCK, s)
            pad = -s % block
            q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
                -1, block, heads, d
            )

            def one(xs):
                q_b, first = xs
                scores = jnp.einsum('qnd,knd->nqk', q_b, k_all) / math.sqrt(d)
                seen = (
                    jnp.arange(s)[None, :] <= (first + jnp.arange(block))[:, None]
                )
                scores = jnp.where(seen[None], scores, -1e30)
                return jnp.einsum(
                    'nqk,knd->qnd', jax.nn.softmax(scores, -1), v_all
                )

            o = jax.lax.map(
                one, (q_blocks, jnp.arange(q_blocks.shape[0]) * block)
            ).reshape(-1, heads * d)[:s]
            o = o * jax.nn.sigmoid(u @ lp['attn_gate'])
            return x + o @ lp['o'], (k, v)

    def route(x, stacks, li):
        """``n``, the gate of every held expert ``[S, E_held]`` (zero where
        a token did not choose it) and ``x + shared(n)`` of layer ``li``."""
        with jax.default_matmul_precision('highest'):
            lp = cut(stacks, _MOE_SMALL, li)
            n = _rms(x, lp['mlp_ln'], eps)
            scores = jax.nn.sigmoid(n @ lp['router'])
            _, chosen = jax.lax.top_k(scores + lp['router_bias'], top_k)
            kept = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = kept / kept.sum(-1, keepdims=True) * routed_scale
            held = _leaf(stacks, 'gate').shape[1]
            gates = jnp.sum(
                jnp.where(
                    (chosen - first_expert)[:, :, None] == jnp.arange(held),
                    weights[:, :, None], 0.0,
                ),
                axis=1,
            )
            shared = (
                jax.nn.silu(n @ lp['shared_gate']) * (n @ lp['shared_up'])
            ) @ lp['shared_down']
            return n, gates, x + shared

    def expert(acc, n, gates, gate, up, down, li, e):
        """``acc + gates[:, e] * swiglu_e(n)`` for held expert ``e``."""
        with jax.default_matmul_precision('highest'):
            w_gate, w_up, w_down = (
                jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(w, li, 0, keepdims=False),
                    e, 0, keepdims=False,
                ).astype(F32)
                for w in (gate, up, down)
            )
            out = (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down
            weight = jax.lax.dynamic_index_in_dim(gates, e, 1, keepdims=True)
            return acc + weight * out

    def head(x_at, scale, kernel):
        with jax.default_matmul_precision('highest'):
            return _rms(x_at, scale.astype(F32), eps) @ kernel.astype(F32)

    return tuple(
        jax.jit(f) for f in (kda, gqa, route, expert, head, recurrence)
    )


def _stacks(tree: dict, names) -> dict:
    return {n: tree[n] for n in names}


def moe_block(moe: dict, model: dict, x, li: int):
    """``x + shared(n) + sum over the chosen HELD experts of w_e
    swiglu_e(n)`` of layer ``li`` for one row ``x [S, hidden]`` float32, one
    held expert a program call. ``moe`` is the experts' tree with the
    chip's share of the banks; ``model['first_local_expert']`` says which
    ids they are."""
    _, _, route, expert, _, _ = _programs(_numbers(model))
    banks = [_leaf(moe, n) for n in _BANKS]
    n, gates, x = route(
        x, {**_stacks(moe, _MOE_SMALL), 'gate': moe['gate']}, jnp.int32(li)
    )
    for e in range(banks[0].shape[1]):
        x = expert(x, n, gates, *banks, jnp.int32(li), jnp.int32(e))
    return x


def forward(params: dict, model: dict, ids, score_at, lengths=None, probe=None):
    """Causal forward over right-padded ``ids [B, S]`` from no state ->
    float32 logits ``[B, P, V]`` at the positions ``score_at [B, P]`` of
    each row, and what each row's sequence holds after its first ``lengths
    [B]`` tokens (all ``S`` by default): a dict a row, ``'kda'`` a list over
    the KDA layers of ``(state [H, d_k, d_v], conv rows [K - 1, 3 H d_k])``,
    ``'gqa'`` a list over the attention layers of ``(k [S, kv_heads, d],
    v)``. Right padding cannot reach an earlier position through a causal
    mask, a causal convolution or a recurrence. ``probe(row, kind, xi, x)``,
    where given, is called with every layer's input ``x [S, hidden]``
    float32 before the layer runs: a check can run the program's layer from
    the reference's own input."""
    ids, score_at = np.asarray(ids), np.asarray(score_at)
    if lengths is None:
        lengths = [ids.shape[1]] * len(ids)
    kda, gqa, _, _, head, _ = _programs(_numbers(model))
    moe = params['moe']
    logits, held = [], []
    for b, (row, at, length) in enumerate(zip(ids, score_at, lengths)):
        x = jnp.asarray(params['embed'])[jnp.asarray(row)].astype(F32)
        holds = {'kda': [], 'gqa': []}
        for li, (kind, xi) in enumerate(layer_kinds(model)):
            if probe is not None:
                probe(b, kind, xi, x)
            if kind == 'kda':
                x, kept = kda(
                    x, _stacks(params['kda'], _KDA), jnp.int32(xi),
                    jnp.int32(length),
                )
            else:
                x, kept = gqa(x, _stacks(params['gqa'], _GQA), jnp.int32(xi))
            holds[kind].append(tuple(np.asarray(t) for t in kept))
            x = moe_block(moe, model, x, li)
        logits.append(np.asarray(head(
            x[jnp.asarray(at)], _leaf(params, 'final_ln'),
            _leaf(params, 'head'),
        )))
        held.append(holds)
    return np.stack(logits), held


def recurrence_state(model: dict, k, v, g, beta):
    """The token-by-token recurrence from a zero state over operands given
    (``k, g [S, H, d_k]``, ``v [S, H, d_v]``, ``beta [S, H]``): the float32
    state ``[H, d_k, d_v]`` after the last position. What a check holds a
    program's chunked or stepped form against from EQUAL operands."""
    return _programs(_numbers(model))[-1](k, v, g, beta)


def solar_open2_logits(params: dict, model: dict, ids, score_at) -> np.ndarray:
    return forward(params, model, ids, score_at)[0]


def compile_ahead(
    model: dict, shapes: dict, widths, scored: int, operands=F32
) -> None:
    """Lower and compile every program that ``forward`` will call for rows
    padded to ``widths`` with ``scored`` positions a row, from the
    parameter tree's ``shapes`` alone (``recurrence`` for ``k, v`` of the
    dtype ``operands``). The results are dropped: the compile
    cache keeps them, so a driver can have this done on a thread while the
    engine is built and warmed. Nothing here changes what they compute."""
    sds = jax.ShapeDtypeStruct
    kda, gqa, route, expert, head, recurrence = _programs(_numbers(model))
    hidden = shapes['embed'].shape[1]
    i32 = sds((), jnp.int32)
    moe = shapes['moe']
    banks = [_leaf(moe, n) for n in _BANKS]
    kinds = {kind for kind, _ in layer_kinds(model)}
    for rows in widths:
        x = sds((rows, hidden), F32)
        if 'kda' in kinds:
            kda.lower(x, _stacks(shapes['kda'], _KDA), i32, i32).compile()
            linear = model['linear_attn_config']
            wide = sds((rows, linear['num_heads'], linear['head_dim']), operands)
            recurrence.lower(
                wide, wide, sds(wide.shape, F32), sds(wide.shape[:2], F32)
            ).compile()
        if 'gqa' in kinds:
            gqa.lower(x, _stacks(shapes['gqa'], _GQA), i32).compile()
        route.lower(
            x, {**_stacks(moe, _MOE_SMALL), 'gate': moe['gate']}, i32
        ).compile()
        gates = sds((rows, banks[0].shape[1]), F32)
        expert.lower(x, x, gates, *banks, i32, i32).compile()
    head.lower(
        sds((scored, hidden), F32), _leaf(shapes, 'final_ln'),
        _leaf(shapes, 'head'),
    ).compile()


def content_error(held, want) -> float:
    """RMS of ``held - want`` over the RMS of ``want``."""
    held, want = np.asarray(held, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((held - want) ** 2).mean() / (want ** 2).mean()))


def bf16_share(held) -> float:
    """Share of the non-zero float32 values of ``held`` that a bfloat16
    holds exactly (their 16 low bits zero): about 2^-16 of a float32
    recurrence's state, all of a state that was rounded to bfloat16 when it
    was written."""
    bits = np.ascontiguousarray(held, np.float32).view(np.uint32).ravel()
    bits = bits[bits << 1 != 0]  # neither +0 nor -0
    return float(((bits & 0xFFFF) == 0).mean()) if bits.size else 1.0


def token_gaps(logits: np.ndarray, outputs) -> np.ndarray:
    """``[B, P]``: how far each generated token's reference logit lies under
    the reference's largest at its position, in standard deviations of that
    position's logits. ``logits [B, P, V]`` are those of ``forward`` at the
    positions that produced ``outputs [B][P]``."""
    z = np.asarray(logits, np.float32)
    tokens = np.asarray(outputs)
    picked = np.take_along_axis(z, tokens[..., None], axis=-1)[..., 0]
    return (z.max(-1) - picked) / z.std(-1)


# The check scores every SCORE_EVERY-th of a row's generated tokens: the
# first (the prefill program's logits) and the last step of every decode
# window after it.
SCORE_EVERY = 8

# The limits, each between the program's largest reading over its seeds and
# the nearest wrong program's; ``benchmarks/SOLAR_OPEN2.md`` has every
# reading (my chip runs, PR 45; one v5e chip, the configuration's widths, the
# cell's own check: the runs of the program and the wrong programs of
# ``scripts/probe_solar_open2_reference.py``). Set from those readings, and
# not widened to fit a run.
#
# Largest gap of the check's 260 scored tokens. Program 0.116-0.394 (near ties
# of the reference's two largest logits over a 24,576-row vocabulary that bf16
# and a turned-over 8th expert move). Softmax scoring 0.67-1.25, beta without
# its 2 1.72 and 1.85, a scalar decay 2.77 and 3.36.
TOKEN_GAP_LIMIT_STD = 0.5
# Mean gap. Program 0.0040-0.0114 (a bf16 state 0.0061-0.0098: it passes, as
# rounding should); softmax scoring 0.083-0.22, beta without its 2 0.36 and
# 0.45, a scalar decay 0.93 and 0.94.
MEAN_GAP_LIMIT_STD = 0.03
# A KDA layer's matrix state in a row's slot, relative RMS error against
# float32, the largest of the ten rows, EVERY KDA layer. Each sits BEHIND
# routed experts: bf16 turns over a token's 8th choice of 320 for one token
# in fifteen, one in fifty with a held expert, the state sums the tokens, and
# every routed layer before a layer adds its own. The limit of a KDA layer
# with ``n`` routed layers before it is ``KDA_STATE_LIMIT + n *
# KDA_STATE_LIMIT_A_LAYER``: 0.12, 0.16, 0.20 for layers 1, 2, 3. Program
# (and the two controls of precision, which read alike here) 0.032-0.075,
# 0.062-0.096, 0.085-0.123; softmax scoring 0.16-0.23, 0.27, 0.34; beta
# without its 2 0.49, a scalar decay 0.70 (layer 1). It holds the equations
# and the slots; it does not see a precision, the next two do.
KDA_STATE_LIMIT = 0.08
KDA_STATE_LIMIT_A_LAYER = 0.04
# The share of a slot's non-zero float32 values that a bfloat16 holds exactly
# (16 low bits zero): that the pool STORES float32, every KDA layer. Program
# 3.9e-5 to 5.5e-5 (2^-16 is 1.5e-5); a state rounded to bfloat16 at every
# write 1.0.
KDA_STATE_BF16_SHARE_LIMIT = 0.01
# The two forms of the program's recurrence (the span form over the engine's
# spans, then the step form) against the reference's token-by-token
# recurrence from EQUAL operands, relative RMS error of the state, the
# largest over rows and KDA layers: no routed expert and no bf16 projection
# between a precision and this reading. Program 1.3e-5 to 6.7e-5 (the rows of
# 16 generated tokens read the most); the span form at the TPU's default
# precision 2.0e-4 to 2.2e-3 (largest 2.2e-3: eleven times this limit), a
# state rounded to bfloat16 at every write 5.4e-3 to 9.8e-3 (forty-nine
# times). NOT correct by this limit, whatever the other readings.
KDA_EQUAL_OPERAND_LIMIT = 2e-4
# The three convolutions' 3 rows in a slot, a KDA layer's MEDIAN over the ten
# rows; the limit is this times the routed layers before the layer (0.03,
# 0.06, 0.09). Program 0.0097-0.0109, 0.016-0.036, 0.034-0.045 (bf16 against
# float32 after one, two and three whole layers); softmax scoring 0.054-0.21,
# 0.104, 0.148 (the experts before a layer feed these rows).
CONV_STATE_LIMIT = 0.03
# ... and the largest row: three positions a row, so one token with a
# turned-over expert reads 0.05-0.10 there; a row that read another slot's
# rows reads about 1.4.
CONV_ROW_LIMIT = 0.5
# Layer 0's K and V in a row's first and last block, the median over the
# rows of the larger of K's and V's error. Program 0.00235-0.00237 (bf16 of a
# float32 row, straight from the embedding); int8 pages read 0.0074 in the
# cells that measured them (``FALCON_H1.md``, ``LFM2.md``; not measured here).
KV_CONTENT_LIMIT = 0.0045
# ... and the largest row: a page that is not the row's reads about 1.4, the
# program's rows at most 0.00243.
KV_ROW_LIMIT = 0.03
