"""The plain reference for ``deepseek_v3`` without query compression
(kakaocorp Kanana-2-30B-A3B): the forward pass of ISSUE 32's equations in
straightforward ``jax.numpy``, in the EXPANDED form of the published code,
weights as stored, everything else float32 under
``jax.default_matmul_precision('highest')``. No absorption, no cache, no
kernels, no batching: one row and one layer at a time (so that it fits
beside the bf16 weights), per-head keys and values made from the latent,
attention as a dense masked softmax computed a block of queries at a time
(so that 8448 tokens fit), the experts as a loop over the held experts with
a per-token weight that is zero where the token did not choose the expert.

It takes the program's parameter tree (``deepseek_v3.init_on_device``'s key
names are all it shares with the code under test) and the configuration
file's published keys, and is given the same share of the experts and of the
vocabulary as the program: ``n_routed_experts`` experts are held, ids
``first_local_expert`` onward of the ``num_routed_experts`` the router ranks;
what the absent ones would add is left out.

For a layer, ``H = num_attention_heads``::

    h = rms(x);  q = h Wq -> [H, 192] = [q_n (128) | q_r (64)]
    a = h Wa -> [576] = [c_raw (512) | k_r (64)];  c = rms(c_raw; g_kv)
    q_r, k_r = rope(q_r, k_r, pos)     all 64 dims; k_r ONE head for all H
    k_n,h = c Wuk_h (128);  v_h = c Wuv_h (128)      [Wuk_h | Wuv_h] = Wb[h]
    s_h(i, j) = (q_n,h(i) . k_n,h(j) + q_r,h(i) . k_r(j)) / sqrt(192), causal
    o_h = sum_j softmax_j(s_h)(i, j) v_h(j);  x = x + concat_h(o_h) Wo
    h2 = rms(x)
    dense layer:   x = x + (silu(h2 Wg) * (h2 Wu)) Wd
    sparse layer:  s = sigmoid(h2 Wr);  S = top_k(s + b)
                   w_e = scale * s_e / (sum_S s + 1e-20)
                   x = x + sum_{e in S} w_e E_e(h2) + E_shared(h2)

The configuration file's ``assumed`` items that touch the arithmetic:
ASSUMED 1, the rotation pairs dims ``(i, i + 32)`` (the half-split order the
published code permutes its interleaved columns to: on seeded weights one
function); ASSUMED 2, ``b`` (``e_score_correction_bias``, a zero buffer
before training) is drawn from the seed, so that a program that drops it
chooses other experts.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_angles(theta: float, rotated: int, positions) -> tuple:
    """``(cos, sin)`` ``[S, rotated / 2]``: ``pos * theta^(-2i / rotated)``."""
    i = np.arange(0, rotated, 2, dtype=np.float64)
    angles = np.asarray(positions, np.float64)[:, None] * (
        float(theta) ** (-i / rotated)
    )[None, :]
    return jnp.asarray(np.cos(angles), F32), jnp.asarray(np.sin(angles), F32)


def _rotate(x, cos, sin):
    """``x [S, N, d]`` rotated in pairs ``(i, i + d / 2)`` (ASSUMED 1)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def latent_rows(h, lp, rank, eps, cos, sin):
    """``(c [S, rank], k_r [S, 1, rope])`` of normed inputs ``h``: what a
    token leaves behind in a layer, the latent normed and the key head
    rotated."""
    a = h @ lp['kv_a']['kernel']
    c = _rms(a[:, :rank], lp['kv_ln']['scale'], eps)
    return c, _rotate(a[:, None, rank:], cos, sin)


def attention(h, lp, heads, nope, rope, v_dim, rank, eps, cos, sin):
    """One row ``h [S, hidden]`` through one attention layer in the
    expanded form, a block of queries at a time."""
    s = h.shape[0]
    q = (h @ lp['q']['kernel']).reshape(s, heads, nope + rope)
    q_n, q_r = q[..., :nope], _rotate(q[..., nope:], cos, sin)
    c, k_r = latent_rows(h, lp, rank, eps, cos, sin)
    k_n = (c @ lp['k_up']['kernel']).reshape(s, heads, nope)
    v = (c @ lp['v_up']['kernel']).reshape(s, heads, v_dim)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, (s, heads, rope))], axis=-1
    )
    q = jnp.concatenate([q_n, q_r], axis=-1)
    j = jnp.arange(s)
    pad = -s % QUERY_BLOCK
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, heads, nope + rope
    )

    def block(args):
        first, q_b = args
        i = first + jnp.arange(QUERY_BLOCK)
        seen = j[None, :] <= i[:, None]  # causal
        scores = jnp.einsum('qnd,knd->nqk', q_b, k) / math.sqrt(nope + rope)
        scores = jnp.where(seen[None], scores, -1e30)
        return jnp.einsum('nqk,knd->qnd', jax.nn.softmax(scores, -1), v)

    firsts = jnp.arange(q_blocks.shape[0]) * QUERY_BLOCK
    o = jax.lax.map(block, (firsts, q_blocks)).reshape(-1, heads, v_dim)[:s]
    return o.reshape(s, heads * v_dim) @ lp['o']['kernel']


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def sparse_mlp(h2, mp, k, scale, first_held):
    """Router over every routed expert, the held experts one after the
    other, the shared experts (one SwiGLU) once."""
    s = jax.nn.sigmoid(h2 @ mp['router']['kernel'])
    # ASSUMED 2: the bias chooses and never weighs.
    _, top_e = jax.lax.top_k(s + mp['router_bias']['kernel'], k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    w = scale * top_s / (top_s.sum(-1, keepdims=True) + 1e-20)

    def one_expert(out, xs):
        e, gate, up, down = xs
        w_e = jnp.where(top_e == e, w, 0.0).sum(-1)  # 0: not chosen
        return out + w_e[:, None] * _swiglu(h2, gate, up, down), None

    held = mp['gate']['kernel'].shape[0]
    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h2),
        (first_held + jnp.arange(held), mp['gate']['kernel'],
         mp['up']['kernel'], mp['down']['kernel']),
    )
    return out + _swiglu(
        h2, mp['shared_gate']['kernel'], mp['shared_up']['kernel'],
        mp['shared_down']['kernel'],
    )


@functools.lru_cache(maxsize=None)
def _programs(heads, nope, rope, v_dim, rank, eps, per_token, scale, first_held):
    """``(layer, head, first_rows)`` jitted once for a model's numbers, so
    that rows of one width and layers of one kind share a compiled program
    (a float32 matmul at the highest precision is 8-10 s of compiling a
    program on a v5e's host)."""

    def layer(x, lp, mp, cos, sin, *, sparse):
        with jax.default_matmul_precision('highest'):
            lp, mp = jax.tree.map(lambda a: a.astype(F32), (lp, mp))
            h = _rms(x, lp['ln']['scale'], eps)
            x = x + attention(h, lp, heads, nope, rope, v_dim, rank, eps, cos, sin)
            h2 = _rms(x, mp['mlp_ln']['scale'], eps)
            if not sparse:
                return x + _swiglu(
                    h2, mp['gate']['kernel'], mp['up']['kernel'],
                    mp['down']['kernel'],
                )
            return x + sparse_mlp(h2, mp, per_token, scale, first_held)

    def head(x, scale_, kernel):
        with jax.default_matmul_precision('highest'):
            return _rms(x, scale_.astype(F32), eps) @ kernel.astype(F32)

    def first_rows(x, lp, cos, sin):
        with jax.default_matmul_precision('highest'):
            lp = jax.tree.map(lambda a: a.astype(F32), lp)
            h = _rms(x.astype(F32), lp['ln']['scale'], eps)
            c, k_r = latent_rows(h, lp, rank, eps, cos, sin)
            return jnp.concatenate([c, k_r[:, 0]], axis=-1)

    return (
        jax.jit(layer, static_argnames=('sparse',)), jax.jit(head),
        jax.jit(first_rows),
    )


def _programs_of(model: dict):
    return _programs(
        model['num_attention_heads'], model['qk_nope_head_dim'],
        model['qk_rope_head_dim'], model['v_head_dim'], model['kv_lora_rank'],
        model['rms_norm_eps'], model['num_experts_per_tok'],
        model['routed_scaling_factor'], model.get('first_local_expert', 0),
    )


def _angles(model: dict, positions):
    return rope_angles(model['rope_theta'], model['qk_rope_head_dim'], positions)


def _mlp_of(model: dict, layer: int) -> tuple[str, int]:
    dense = min(model['first_k_dense_replace'], model['num_hidden_layers'])
    return ('dense', layer) if layer < dense else ('sparse', layer - dense)


_FIRST_ROWS = ('ln', 'kv_a', 'kv_ln')  # what layer 0's cache rows read


def deepseek_logits(params: dict, model: dict, ids, score_at) -> np.ndarray:
    """Causal forward over right-padded ``ids [B, S]`` from no state ->
    float32 logits ``[B, P, V]`` at the positions ``score_at [B, P]`` of
    each row. Right padding cannot reach an earlier position through a
    causal mask, so no padding mask is needed."""
    ids, score_at = np.asarray(ids), np.asarray(score_at)
    cos, sin = _angles(model, np.arange(ids.shape[1]))
    layer, head, _ = _programs_of(model)
    out = []
    for row, at in zip(ids, score_at):
        x = jnp.asarray(params['embed'])[jnp.asarray(row)].astype(F32)
        for li in range(model['num_hidden_layers']):
            mlp, mi = _mlp_of(model, li)
            x = layer(
                x, jax.tree.map(lambda a: a[li], params['attn']),
                jax.tree.map(lambda a: a[mi], params[mlp]), cos, sin,
                sparse=mlp == 'sparse',
            )
        out.append(np.asarray(
            head(x[jnp.asarray(at)], params['final_ln']['scale'],
                 params['lm_head'])
        ))
    return np.stack(out)


def first_layer_rows(params: dict, model: dict, ids, positions) -> np.ndarray:
    """Float32 ``[T, kv_lora_rank + qk_rope_head_dim]``: the rows layer 0
    writes into its plane for tokens ``ids [T]`` at ``positions [T]`` (the
    latent normed, the key head rotated). Layer 0 reads the embedding alone,
    so its rows are a function of a token and its position and of nothing
    the row attended to: the one place where the pool's CONTENT can be held
    to float32 without the program's own noise from the layers below."""
    cos, sin = _angles(model, positions)
    lp = {n: jax.tree.map(lambda a: a[0], params['attn'][n]) for n in _FIRST_ROWS}
    return np.asarray(_programs_of(model)[2](
        jnp.asarray(params['embed'])[jnp.asarray(ids)], lp, cos, sin
    ))


def compile_ahead(model: dict, shapes: dict, widths, scored: int, kv_rows: int):
    """Lower and compile every program that ``deepseek_logits`` (rows padded
    to ``widths``, ``scored`` positions a row) and ``first_layer_rows``
    (``kv_rows`` tokens a call) will call, from the parameter tree's
    ``shapes`` alone. The results are dropped: the compile cache keeps them,
    so a driver can have this done on a thread while the engine is built and
    warmed, and the check then finds its programs compiled. Nothing here
    changes what they compute."""
    sds = jax.ShapeDtypeStruct
    layer, head, first_rows = _programs_of(model)
    hidden, half = shapes['embed'].shape[1], model['qk_rope_head_dim'] // 2

    def one(tree):  # a layer of a stacked tree
        return jax.tree.map(lambda a: sds(a.shape[1:], a.dtype), tree)

    kinds = sorted({
        _mlp_of(model, li)[0] for li in range(model['num_hidden_layers'])
    })
    for width in widths:
        for mlp in kinds:
            layer.lower(
                sds((width, hidden), F32), one(shapes['attn']),
                one(shapes[mlp]), *(sds((width, half), F32),) * 2,
                sparse=mlp == 'sparse',
            ).compile()
    head.lower(
        sds((scored, hidden), F32), shapes['final_ln']['scale'],
        shapes['lm_head'],
    ).compile()
    first_rows.lower(
        sds((kv_rows, hidden), shapes['embed'].dtype),
        {n: one(shapes['attn'][n]) for n in _FIRST_ROWS},
        *(sds((kv_rows, half), F32),) * 2,
    ).compile()


def row_content_error(held, want) -> float:
    """RMS of ``held - want`` over the RMS of ``want``."""
    held, want = np.asarray(held, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((held - want) ** 2).mean() / (want ** 2).mean()))


def token_gaps(logits: np.ndarray, outputs) -> np.ndarray:
    """``[B, P]``: how far each generated token's reference logit lies under
    the reference's largest at its position, in standard deviations of that
    position's logits. ``logits [B, P, V]`` are those of ``deepseek_logits``
    at the positions that produced ``outputs [B][P]``."""
    z = np.asarray(logits, np.float32)
    tokens = np.asarray(outputs)
    picked = np.take_along_axis(z, tokens[..., None], axis=-1)[..., 0]
    return (z.max(-1) - picked) / z.std(-1)


# Four limits, calibrated on the chip at the configuration's widths through
# the cell's own check (8 rows x 256 tokens of one greedy call of 48 prompts;
# ``scripts/probe_deepseek_reference.py`` builds each wrong program and runs
# this very check; my chip runs, PR 32; ``PERF.md`` section 6 has every
# reading). The gaps are ten times laguna's, and not by a fault: the router
# ranks 128 sigmoid scores whose 6th and 7th largest lie 0.009 apart under
# N(0, 0.02) weights, so bf16's rounding of a layer's input changes the kept
# set for some tokens in every layer, and a changed expert moves every logit
# (12 layers with 6 experts of 6 kept, where nothing can flip, read 0.015 of
# a logit's spread where 6 of 128 read 0.080). A wrong program moves them all
# the time, which is why the MEAN gap tells the two apart best.
#
# ``MEAN_GAP_LIMIT_STD`` 0.07 on the mean gap of all 2048 positions: the
# program reads 0.0241-0.0291 over ten seeds (single rows 0.012-0.041); the
# selection bias left out 0.183, softmax scoring 0.196, the 2.448 left out
# 0.229, the scale ``128 ** -0.5`` 0.342, ``kv_a_layernorm`` left out 0.346,
# ``k_r`` left out of the scores 0.946, the kept scores not renormalised 3.14,
# values read from lanes 64-575 3.92: the limit lies 2.4 times over the one
# and 2.6 times under the nearest of the others. An average wanders little
# (ten seeds within 0.005), so this is the limit that every wrong program of
# the list but one fails, and the two below are set for what it would miss.
# ``ROW_GAP_LIMIT_STD`` 1.5 on the median over the 8 rows of each row's
# LARGEST gap (the program 0.621-0.900 over ten seeds; 8 of 88 single rows
# read over 1.2 and one over 1.5, so a limit near 1.1 would refuse about one
# right run in a hundred): the scale 1.81, ``kv_a_layernorm`` 1.85, ``k_r``
# 3.10, no renormalising 6.00, the value lanes 6.97; the three router faults
# (1.33, 1.39, 1.42) pass it and fail the mean. ``TOKEN_GAP_LIMIT_STD`` 3.2 on
# the largest of all positions is for a fault in one row alone, which a median
# and a mean of eight rows would miss: the program reads 0.90-1.65 (an extreme
# of 2048 near ties with a changed expert among them, which wanders), the
# gross faults 3.69, 7.18 and 7.78.
#
# ``ROW_CONTENT_LIMIT`` 0.0048 is the precision limit: layer 0's latent rows in
# each scored row's first and last block of the pool against
# ``first_layer_rows`` (relative RMS error over the 576 lanes a row uses, the
# median over the rows). The bf16 program reads 0.00287-0.00290 over ten seeds
# (every row 0.00282-0.00294); the nearest precision below, every row rounded to int8
# with one scale a token before it enters the pool, 0.00819 (0.00798-0.00853;
# its gaps, mean 0.038 and row median 0.861, pass the limits above, so this
# one alone holds the pool to bf16); ``kv_a_layernorm`` left out reads 0.0955.
TOKEN_GAP_LIMIT_STD = 3.2
ROW_GAP_LIMIT_STD = 1.5
MEAN_GAP_LIMIT_STD = 0.07
ROW_CONTENT_LIMIT = 0.0048
