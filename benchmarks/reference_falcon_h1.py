"""The plain reference for ``falcon_h1`` (tiiuae Falcon-H1): the forward pass
of ISSUE 41's equations in straightforward ``jax.numpy``, weights as stored,
everything else float32 under ``jax.default_matmul_precision('highest')``.
No cache, no state carried, no kernels, no batching, no chunked scan: one row
at a time, the Mamba-2 recurrence one time step after the other, attention as
a dense masked softmax over the whole row. It shares no code with
``distllm_tpu/models/``; the parameter tree's key names
(``falcon_h1.init_on_device``'s) and the configuration file's published keys
are all it takes from the program.

Computed in blocks so that it fits beside nothing but the bf16 weights: a
layer's mixer weights are cut out of their stacks and cast up one layer at a
time (0.4 GB in float32), the MLP goes by ``MLP_BLOCK``
columns of its width, and the head by ``HEAD_BLOCK`` rows of the vocabulary
at the scored positions alone, each cut out of its stack inside the program
that reads it: under 1 GB beside the weights at the published widths.

For a layer on ``x [S, hidden]`` (ASSUMED n: the configuration file's
``assumed`` item n)::

    h = rms(x; input_layernorm)                 both mixers read this h
    Mamba-2: p = (h * ssm_in_multiplier) W_in
             [z | x | B | C | dt] = p, in that order (ASSUMED 1), each part
             times its ssm_multipliers entry, in that order (ASSUMED 2)
             [x | B | C] = silu(causal_conv4([x | B | C]) + b)
             dt = softplus(dt + dt_bias); A = -exp(A_log)
             head i reads B_g, C_g of group g = i // (heads / groups)
             (ASSUMED 3)
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t
             g = y * silu(z); RMSNorm over each group's channels apart with
             the learned scale (ASSUMED 4)
             m = (g W_out) * ssm_out_multiplier
    attention: q = (h * attention_in_multiplier) W_q
               k = (h W_k) * key_multiplier; v = h W_v
               rope, theta rope_theta, over all head_dim dims in pairs
               (i, i + d/2) (ASSUMED 5); causal; scores / sqrt(head_dim)
               a = (attn W_o) * attention_out_multiplier
    x = x + m + a;  h2 = rms(x; pre_ff_layernorm)
    x = x + (W_down(silu(W_gate h2 * mlp_multipliers[0]) * W_up h2))
            * mlp_multipliers[1]
    logits = (rms(x; final_layernorm) W_head) * lm_head_multiplier
                                                  (ASSUMED 6: untied head)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MLP_BLOCK = 5376  # columns of the MLP's width a call (a quarter as published)
HEAD_BLOCK = 16320  # rows of the vocabulary a call (a sixteenth as published)

# The mixers' leaves of a layer, cut out of their stacks one layer at a time.
_MIXER = (
    'ln', 'mlp_ln', 'q', 'k', 'v', 'o', 'in_proj', 'conv', 'conv_bias',
    'dt_bias', 'A_log', 'D', 'norm', 'out_proj',
)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_angles(theta: float, d: int, positions) -> tuple:
    """``(cos, sin)`` ``[S, d / 2]`` of ``pos * theta^(-2i / d)``, reckoned
    in float64 (at theta 1e11 the slowest pair turns 1.5e-11 a token)."""
    i = np.arange(0, d, 2, dtype=np.float64)
    angles = np.asarray(positions, np.float64)[:, None] * (
        float(theta) ** (-i / d)
    )[None, :]
    return jnp.asarray(np.cos(angles), F32), jnp.asarray(np.sin(angles), F32)


def _rotate(x, cos, sin):
    """``x [S, N, d]`` rotated in pairs ``(i, i + d / 2)`` (ASSUMED 5)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _numbers(model: dict) -> tuple:
    """The model's numbers as a hashable tuple (a jitted program a model)."""
    keys = (
        'num_attention_heads', 'num_key_value_heads', 'head_dim',
        'mamba_n_heads', 'mamba_d_head', 'mamba_d_state', 'mamba_n_groups',
        'mamba_d_conv', 'rms_norm_eps', 'attention_in_multiplier',
        'attention_out_multiplier', 'key_multiplier', 'ssm_in_multiplier',
        'ssm_out_multiplier', 'lm_head_multiplier', 'embedding_multiplier',
    )
    return tuple(model[k] for k in keys) + (
        tuple(model['ssm_multipliers']), tuple(model['mlp_multipliers']),
    )


@functools.lru_cache(maxsize=None)
def _programs(numbers: tuple):
    (heads, kv_heads, d, m_heads, m_p, m_n, m_g, m_k, eps, attn_in, attn_out,
     key_mult, ssm_in, ssm_out, head_mult, _embed_mult, ssm_mults,
     mlp_mults) = numbers
    d_inner = m_heads * m_p
    gn = m_g * m_n

    def mamba(h, lp, length):
        """The mixer's output for one row ``h [S, hidden]`` and what a
        sequence holds of the layer after ``length`` tokens: the SSM state
        ``[heads, P, N]`` and the convolution's last ``K - 1`` inputs."""
        s = h.shape[0]
        p = (h * ssm_in) @ lp['in_proj']['kernel']
        z, x, b_in, c_in, dt = jnp.split(
            p, np.cumsum([d_inner, d_inner, gn, gn]).tolist(), axis=-1
        )  # ASSUMED 1
        z, x, b_in, c_in, dt = (
            part * mult for part, mult in zip((z, x, b_in, c_in, dt), ssm_mults)
        )  # ASSUMED 2
        xbc = jnp.concatenate([x, b_in, c_in], axis=-1)
        padded = jnp.pad(xbc, ((m_k - 1, 0), (0, 0)))
        conv_rows = jax.lax.dynamic_slice_in_dim(padded, length, m_k - 1, 0)
        conv = sum(lp['conv'][j] * padded[j:j + s] for j in range(m_k))
        xbc = jax.nn.silu(conv + lp['conv_bias'])
        x = xbc[:, :d_inner].reshape(s, m_heads, m_p)
        # ASSUMED 3: a group's B and C for the heads of the group
        b_in = jnp.repeat(
            xbc[:, d_inner:d_inner + gn].reshape(s, m_g, m_n), m_heads // m_g, 1
        )
        c_in = jnp.repeat(
            xbc[:, d_inner + gn:].reshape(s, m_g, m_n), m_heads // m_g, 1
        )
        dt = jax.nn.softplus(dt + lp['dt_bias'])  # [S, heads]
        a = -jnp.exp(lp['A_log'])

        def step(carry, xs):
            state, kept = carry
            t, x_t, dt_t, b_t, c_t = xs  # [heads, P], [heads], [heads, N] x2
            state = (
                state * jnp.exp(dt_t * a)[:, None, None]
                + (dt_t[:, None] * x_t)[..., None] * b_t[:, None, :]
            )
            y_t = (state * c_t[:, None, :]).sum(-1) + lp['D'][:, None] * x_t
            kept = jnp.where(t < length, state, kept)
            return (state, kept), y_t

        zeros = jnp.zeros((m_heads, m_p, m_n), F32)
        (_, kept), y = jax.lax.scan(
            step, (zeros, zeros), (jnp.arange(s), x, dt, b_in, c_in)
        )
        g = y.reshape(s, d_inner) * jax.nn.silu(z)
        g = _rms(
            g.reshape(s, m_g, d_inner // m_g),
            lp['norm']['scale'].reshape(m_g, d_inner // m_g), eps,
        ).reshape(s, d_inner)  # ASSUMED 4
        return (g @ lp['out_proj']['kernel']) * ssm_out, kept, conv_rows

    def attention(h, lp, cos, sin):
        s = h.shape[0]
        q = ((h * attn_in) @ lp['q']['kernel']).reshape(s, heads, d)
        k = ((h @ lp['k']['kernel']) * key_mult).reshape(s, kv_heads, d)
        v = (h @ lp['v']['kernel']).reshape(s, kv_heads, d)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        group = heads // kv_heads
        k_all, v_all = (jnp.repeat(t, group, axis=1) for t in (k, v))
        scores = jnp.einsum('qnd,knd->nqk', q, k_all) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None], scores, -1e30)
        o = jnp.einsum('nqk,knd->qnd', jax.nn.softmax(scores, -1), v_all)
        return (o.reshape(s, heads * d) @ lp['o']['kernel']) * attn_out, k, v

    def mix(x, stacks, li, cos, sin, length):
        """``x + m + a`` of layer ``li``, ``h2`` for its MLP, and what a
        sequence holds of the layer: ``(state, conv rows, k, v)``."""
        with jax.default_matmul_precision('highest'):
            lp = jax.tree.map(lambda w: _cut(w, li).astype(F32), stacks)
            h = _rms(x, lp['ln']['scale'], eps)
            m, state, conv_rows = mamba(h, lp, length)
            a, k, v = attention(h, lp, cos, sin)
            x = x + m + a
            return x, _rms(x, lp['mlp_ln']['scale'], eps), (state, conv_rows, k, v)

    def mlp_block(acc, h2, gate, up, down, li, lo, *, block):
        """``acc +`` the MLP's columns ``lo`` to ``lo + block`` of layer
        ``li``: exact, the width only meets in the sum."""
        with jax.default_matmul_precision('highest'):
            gate, up = (
                _cut(w, li, lo, block, axis=1).astype(F32) for w in (gate, up)
            )
            down = _cut(down, li, lo, block, axis=0).astype(F32)
            hidden = jax.nn.silu((h2 @ gate) * mlp_mults[0]) * (h2 @ up)
            return acc + (hidden @ down) * mlp_mults[1]

    def final_norm(x, scale):
        return _rms(x, scale.astype(F32), eps)

    def head_block(hn, head, lo, *, block):
        with jax.default_matmul_precision('highest'):
            head = jax.lax.dynamic_slice_in_dim(head, lo, block, 1)
            return (hn @ head.astype(F32)) * head_mult  # ASSUMED 6

    return (
        jax.jit(mix), jax.jit(mlp_block, static_argnames='block'),
        jax.jit(final_norm), jax.jit(head_block, static_argnames='block'),
    )


def _cut(stack, li, lo=None, size=None, axis=0):
    """Layer ``li`` of a stacked leaf, and of it ``size`` rows or columns
    from ``lo`` on where given: cut inside the program that reads it, so
    that no copy of a stack or of a layer is made beside it."""
    leaf = jax.lax.dynamic_index_in_dim(stack, li, 0, keepdims=False)
    if lo is None:
        return leaf
    return jax.lax.dynamic_slice_in_dim(leaf, lo, size, axis)


def _blocks(width: int, block: int) -> list[tuple[int, int]]:
    """``(first, size)`` of the blocks a width is walked in."""
    return [(lo, min(block, width - lo)) for lo in range(0, width, block)]


def _mixer_stacks(layers: dict) -> dict:
    return {n: layers[n] for n in _MIXER}


def forward(params: dict, model: dict, ids, score_at, lengths=None):
    """Causal forward over right-padded ``ids [B, S]`` from no state ->
    float32 logits ``[B, P, V]`` at the positions ``score_at [B, P]`` of
    each row, and what each row's sequence holds of LAYER 0 after its first
    ``lengths [B]`` tokens (all ``S`` by default): a list of ``(ssm state
    [heads, P, N], conv rows [K - 1, conv_dim], k [S, kv_heads, d], v)``,
    ``k`` after the multiplier and the rotation, as a page holds it. Right
    padding cannot reach an earlier position through a causal mask, a causal
    convolution or a recurrence."""
    ids, score_at = np.asarray(ids), np.asarray(score_at)
    if lengths is None:
        lengths = [ids.shape[1]] * len(ids)
    mix, mlp_block, final_norm, head_block = _programs(_numbers(model))
    cos, sin = rope_angles(
        model['rope_theta'], model['head_dim'], np.arange(ids.shape[1])
    )
    layers = params['layers']
    width = layers['gate']['kernel'].shape[-1]
    vocab = params['head']['kernel'].shape[-1]
    logits, held = [], []
    for row, at, length in zip(ids, score_at, lengths):
        x = jnp.asarray(params['embed'])[jnp.asarray(row)].astype(F32)
        x = x * model['embedding_multiplier']
        for li in range(model['num_hidden_layers']):
            x, h2, holds = mix(
                x, _mixer_stacks(layers), jnp.int32(li), cos, sin,
                jnp.int32(length),
            )
            if li == 0:
                held.append(tuple(np.asarray(t) for t in holds))
            for lo, size in _blocks(width, MLP_BLOCK):
                x = mlp_block(
                    x, h2, layers['gate']['kernel'], layers['up']['kernel'],
                    layers['down']['kernel'], jnp.int32(li), jnp.int32(lo),
                    block=size,
                )
        hn = final_norm(x[jnp.asarray(at)], params['final_ln']['scale'])
        logits.append(np.concatenate([
            np.asarray(head_block(
                hn, params['head']['kernel'], jnp.int32(lo), block=size
            ))
            for lo, size in _blocks(vocab, HEAD_BLOCK)
        ], axis=-1))
    return np.stack(logits), held


def falcon_h1_logits(params: dict, model: dict, ids, score_at) -> np.ndarray:
    return forward(params, model, ids, score_at)[0]


def compile_ahead(model: dict, shapes: dict, widths, scored: int) -> None:
    """Lower and compile every program that ``forward`` will call for rows
    padded to ``widths`` with ``scored`` positions a row, from the
    parameter tree's ``shapes`` alone. The results are dropped: the compile
    cache keeps them, so a driver can have this done on a thread while the
    engine is built and warmed. Nothing here changes what they compute."""
    sds = jax.ShapeDtypeStruct
    mix, mlp_block, final_norm, head_block = _programs(_numbers(model))
    layers, hidden = shapes['layers'], shapes['embed'].shape[1]
    half = model['head_dim'] // 2
    i32 = sds((), jnp.int32)
    banks = [layers[n]['kernel'] for n in ('gate', 'up', 'down')]
    width = banks[0].shape[-1]
    vocab = shapes['head']['kernel'].shape[-1]
    for rows in widths:
        x = sds((rows, hidden), F32)
        mix.lower(
            x, _mixer_stacks(layers), i32, *(sds((rows, half), F32),) * 2, i32
        ).compile()
        for size in sorted({size for _, size in _blocks(width, MLP_BLOCK)}):
            mlp_block.lower(x, x, *banks, i32, i32, block=size).compile()
    hn = sds((scored, hidden), F32)
    final_norm.lower(hn, shapes['final_ln']['scale']).compile()
    for size in sorted({size for _, size in _blocks(vocab, HEAD_BLOCK)}):
        head_block.lower(hn, shapes['head']['kernel'], i32, block=size).compile()


def content_error(held, want) -> float:
    """RMS of ``held - want`` over the RMS of ``want``."""
    held, want = np.asarray(held, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((held - want) ** 2).mean() / (want ** 2).mean()))


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
# window after it: 16 positions a row of the first call's 8 rows and 2 of
# each second holder's 16 tokens, 132 a check.
SCORE_EVERY = 8

# The limits, each between the program's largest reading over its seeds and
# the nearest wrong program's; ``benchmarks/FALCON_H1.md`` has every reading
# (my chip runs, PR 41; one v5e chip, the configuration's widths, the cell's
# own check). A limit was set once, from those readings, and is not widened
# to fit a run.
#
# Largest gap of the check's 132 scored tokens. Program, the four seeds the
# limits were set on: 0.007-0.020; fifteen runs since: 0.001-0.025 (near
# ties of the reference's two largest logits that bf16 turns over). The
# gated norm over all channels 0.63, the rotation dropped
# 0.70, heads 16-31 on group 0's B and C 0.81, no attention half 1.08.
TOKEN_GAP_LIMIT_STD = 0.3
# Mean gap. Program 0.00007-0.00026 (0.00002-0.00047 over fifteen runs; a
# bf16 SSM state 0.00033, int8 pages 0.00055: both pass it, as rounding
# should); the rotation dropped 0.062,
# group 0's B and C 0.070, the norm over all channels 0.100 (the one fault
# that only the logits show), no attention half 0.17, no Mamba half 1.97.
MEAN_GAP_LIMIT_STD = 0.01
# Layer 0's SSM state in a row's slot, relative RMS error against float32, the
# largest of the rows. Program 0.0007-0.00135 (bf16 inputs of a float32
# recurrence). The state rounded to bfloat16 at every write 0.0046-0.0068 a
# row, 0.0068 the largest: NOT correct, and this limit alone says so. A slot
# not zeroed for its second holder 0.037 and 0.065 in those two rows, group
# 0's B and C 0.94, the five-part multiplier dropped 26.7.
SSM_STATE_LIMIT = 0.003
# The convolution's 3 rows in the slot: bf16 against float32 is 0.00238 on
# every seed (the rounding itself); the five-part multiplier dropped 2.66.
CONV_STATE_LIMIT = 0.006
# Layer 0's K and V in a row's first and last block, the median over the
# rows of the larger of K's and V's error. Program 0.00305-0.00309 (bf16 of a
# float32 row); int8 pages, one scale a token and head, 0.00738: NOT correct,
# by this limit alone. The rotation dropped 0.74, ``key_multiplier`` dropped
# 89.5.
KV_CONTENT_LIMIT = 0.0045
# ... and the largest row: a page that is not the row's reads about 1.4
# (``benchmarks/LFM2.md``), the program's rows at most 0.0033, int8's 0.0076.
KV_ROW_LIMIT = 0.03
