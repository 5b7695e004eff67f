"""The plain reference for ``ouro`` (ByteDance Ouro looped decoders,
arXiv:2510.25741): the full forward pass of ISSUE 48's equations in
straightforward ``jax.numpy``, weights as stored, everything else float32
under ``jax.default_matmul_precision('highest')``. A Python loop over the
passes and, inside it, over the layers; attention as a dense masked softmax
over the whole row; no cache, no kernels. It shares no code with
``distllm_tpu/models/`` or ``distllm_tpu/ops/``; the parameter tree's key
names (``ouro.init_on_device``'s) and the configuration file's published keys
are all it takes from the program.

With ``x_0 = E[ids]``, ``L`` layers, ``T = total_ut_steps`` (ASSUMED n: the
configuration file's ``assumed`` item n), for ``t = 0 .. T - 1``::

    for l = 0 .. L - 1, the SAME weights in every pass:
        u = rms(x; attn_ln)                          input_layernorm
        q, k, v = u W_q, u W_k, u W_v                no bias (ASSUMED 5)
        rope(q), rope(k): theta rope_theta over the whole head in pairs
        (i, i + d/2), the position's own angle in every pass
        plane t L + l holds this k and v (ASSUMED 3, 4)
        a = causal softmax(q k^T / sqrt(d)) v        keys of pass t alone
        h = x + rms(a W_o; post_attn_ln)             input_layernorm_2
        m = rms(h; mlp_ln)                           post_attention_layernorm
        x = h + rms((silu(m W_gate) * m W_up) W_down; post_mlp_ln)
                                                     post_attention_layernorm_2
                                                     (ASSUMED 1)
    z_t = rms(x; final_ln); g_t = w_g . z_t + b_g;  x <- z_t    (ASSUMED 2, 6)

    lambda_t = sigmoid(g_t); p_t = lambda_t prod_{j<t}(1 - lambda_j) for
    t < T - 1, p_{T-1} what is left; the head reads z_e, e the first t with
    p_0 + .. + p_t >= early_exit_threshold, else T - 1 (ASSUMED 7)
    logits = z_e W_head                              untied

Rows go ``ROW_BLOCK`` at a time, a layer's weights are cut out of their
stacks and cast up one layer at a time (0.2 GB in float32 as published), and
the head reads the scored positions alone: under 2 GB beside the bf16
weights at the published widths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 4  # rows of the check a call


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_angles(theta: float, d: int, positions) -> tuple:
    """``(cos, sin)`` ``[S, d / 2]`` of ``pos * theta^(-2i / d)``, reckoned
    in float64."""
    i = np.arange(0, d, 2, dtype=np.float64)
    angles = np.asarray(positions, np.float64)[:, None] * (
        float(theta) ** (-i / d)
    )[None, :]
    return jnp.asarray(np.cos(angles), F32), jnp.asarray(np.sin(angles), F32)


def _rotate(x, cos, sin):
    """``x [B, S, N, d]`` rotated in pairs ``(i, i + d / 2)``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _numbers(model: dict) -> tuple:
    heads = model['num_attention_heads']
    return (
        heads, model.get('num_key_value_heads', heads),
        model.get('head_dim') or model['hidden_size'] // heads,
        float(model['rms_norm_eps']),
    )


@functools.lru_cache(maxsize=None)
def _programs(numbers: tuple):
    heads, kv_heads, d, eps = numbers

    @jax.jit
    def layer(x, stacks, li, cos, sin):
        """One layer over ``x [B, S, H]`` with layer ``li``'s weights cut out
        of ``stacks``: the new ``x`` and the layer's rotated keys and its
        values ``[B, S, kv_heads, d]``, what its plane holds."""
        with jax.default_matmul_precision('highest'):
            lp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, False).astype(F32),
                stacks,
            )
            b, s, _ = x.shape
            u = _rms(x, lp['attn_ln']['scale'], eps)
            q = (u @ lp['q']['kernel']).reshape(b, s, heads, d)
            k = (u @ lp['k']['kernel']).reshape(b, s, kv_heads, d)
            v = (u @ lp['v']['kernel']).reshape(b, s, kv_heads, d)
            q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
            rep = heads // kv_heads
            scores = jnp.einsum(
                'bqnd,bknd->bnqk', q, jnp.repeat(k, rep, axis=2)
            ) / math.sqrt(d)
            causal = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(causal[None, None], scores, -1e30)
            a = jnp.einsum(
                'bnqk,bknd->bqnd', jax.nn.softmax(scores, -1),
                jnp.repeat(v, rep, axis=2),
            ).reshape(b, s, heads * d)
            h = x + _rms(a @ lp['o']['kernel'], lp['post_attn_ln']['scale'], eps)
            m = _rms(h, lp['mlp_ln']['scale'], eps)
            mlp = (
                jax.nn.silu(m @ lp['gate']['kernel']) * (m @ lp['up']['kernel'])
            ) @ lp['down']['kernel']
            return h + _rms(mlp, lp['post_mlp_ln']['scale'], eps), k, v

    @jax.jit
    def close(x, scale, gate_kernel, gate_bias):
        """What ends a pass: ``z = rms(x; final_ln)`` and the gate's value
        ``w_g . z + b_g``, an exact float32 sum of products."""
        z = _rms(x, scale.astype(F32), eps)
        g = (z * gate_kernel.astype(F32)[:, 0]).sum(-1) + gate_bias.astype(F32)[0]
        return z, g

    @jax.jit
    def head(z, kernel):
        with jax.default_matmul_precision('highest'):
            return z @ kernel.astype(F32)

    return layer, close, head


def exit_passes(gates, threshold: float) -> np.ndarray:
    """The exit rule: ``gates [T, ...]`` float32 -> the pass each token's
    head reads, in float32 arithmetic one pass after the other."""
    gates = np.asarray(gates, np.float32)
    passes = len(gates)
    lam = (1.0 / (1.0 + np.exp(-gates.astype(np.float64)))).astype(np.float32)
    survive = np.ones(gates.shape[1:], np.float32)
    cum = np.zeros(gates.shape[1:], np.float32)
    chosen = np.full(gates.shape[1:], passes - 1, np.int32)
    done = np.zeros(gates.shape[1:], bool)
    for t in range(passes):
        p = survive if t == passes - 1 else lam[t] * survive
        cum = (cum + p).astype(np.float32)
        hit = ~done & (cum >= np.float32(threshold))
        chosen[hit] = t
        done |= hit
        survive = (survive * (np.float32(1.0) - lam[t])).astype(np.float32)
    return chosen


def forward(
    params: dict, model: dict, ids, score_at, planes=(), passes: bool = False,
    threshold: float | None = None,
) -> dict:
    """Causal forward over right-padded ``ids [B, S]`` (right padding
    cannot reach an earlier position through a causal mask). Returns a dict:
    ``logits [B, P, V]`` float32 at the positions ``score_at [B, P]`` of each
    row, read from the pass the exit rule chose for each of them;
    ``exit_pass [B, S]``; ``planes``: ``{p: (k, v)}`` for every plane ``p =
    t * L + l`` asked for, ``[B, S, kv_heads, d]`` each, the keys rotated,
    as a page holds them; with ``passes`` also ``z [T, B, S, H]`` and ``gate
    [T, B, S]``, every pass's normed output and its gate's value.
    ``threshold`` stands in for the configuration's ``early_exit_threshold``."""
    ids, score_at = np.asarray(ids), np.asarray(score_at)
    layer, close, head = _programs(_numbers(model))
    num_layers, total = model['num_hidden_layers'], model['total_ut_steps']
    if threshold is None:
        threshold = model['early_exit_threshold']
    cos, sin = rope_angles(
        model['rope_theta'], _numbers(model)[2], np.arange(ids.shape[1])
    )
    gate = params['exit_gate']
    out = {'logits': [], 'exit_pass': [], 'planes': {p: ([], []) for p in planes}}
    if passes:
        out.update(z=[], gate=[])
    for lo in range(0, len(ids), ROW_BLOCK):
        rows = jnp.asarray(ids[lo:lo + ROW_BLOCK])
        at = score_at[lo:lo + ROW_BLOCK]
        x = jnp.asarray(params['embed'])[rows].astype(F32)
        zs, z_at, gates = [], [], []
        for t in range(total):
            for li in range(num_layers):
                x, k, v = layer(x, params['layers'], jnp.int32(li), cos, sin)
                held = out['planes'].get(t * num_layers + li)
                if held is not None:
                    held[0].append(np.asarray(k))
                    held[1].append(np.asarray(v))
            x, g = close(
                x, params['final_ln']['scale'], gate['kernel'], gate['bias']
            )
            # every pass's output at the scored positions; whole with ``passes``
            z_at.append(jnp.take_along_axis(x, at[..., None], 1))
            if passes:
                zs.append(np.asarray(x))
            gates.append(np.asarray(g))
        chosen = exit_passes(np.stack(gates), threshold)  # [rows, S]
        out['exit_pass'].append(chosen)
        pick = np.take_along_axis(chosen, at, 1)  # [rows, P]
        z_e = jnp.take_along_axis(
            jnp.stack(z_at), pick[None, ..., None], 0  # [T, rows, P, H]
        )[0]
        out['logits'].append(np.asarray(head(z_e, params['lm_head'])))
        if passes:
            out['z'].append(np.stack(zs))
            out['gate'].append(np.stack(gates))
    out['logits'] = np.concatenate(out['logits'])
    out['exit_pass'] = np.concatenate(out['exit_pass'])
    out['planes'] = {
        p: (np.concatenate(k), np.concatenate(v))
        for p, (k, v) in out['planes'].items()
    }
    if passes:
        out['z'] = np.concatenate(out['z'], axis=1)
        out['gate'] = np.concatenate(out['gate'], axis=1)
    return out


def ouro_logits(params: dict, model: dict, ids, score_at) -> np.ndarray:
    return forward(params, model, ids, score_at)['logits']


def compile_ahead(
    model: dict, shapes: dict, width: int, scored: int, rows: int
) -> None:
    """Lower and compile every program that ``forward`` will call for
    ``rows`` rows padded to ``width`` with ``scored`` positions a row, from
    the parameter tree's ``shapes`` alone. The results are dropped: the
    compile cache keeps them, so a driver can have this done on a thread
    while the engine is built and warmed."""
    sds = jax.ShapeDtypeStruct
    layer, close, head = _programs(_numbers(model))
    hidden = shapes['embed'].shape[1]
    half = _numbers(model)[2] // 2
    gate = shapes['exit_gate']
    for n in sorted({min(ROW_BLOCK, rows), rows % ROW_BLOCK or ROW_BLOCK}):
        x = sds((n, width, hidden), F32)
        layer.lower(
            x, shapes['layers'], sds((), jnp.int32),
            *(sds((width, half), F32),) * 2,
        ).compile()
        close.lower(
            x, shapes['final_ln']['scale'], gate['kernel'], gate['bias']
        ).compile()
        head.lower(sds((n, scored, hidden), F32), shapes['lm_head']).compile()


def content_error(held, want) -> float:
    """RMS of ``held - want`` over the RMS of ``want``."""
    held, want = np.asarray(held, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((held - want) ** 2).mean() / (want ** 2).mean()))


def token_gaps(logits: np.ndarray, outputs) -> np.ndarray:
    """``[B, P]``: how far each generated token's reference logit lies under
    the reference's largest at its position, in standard deviations of that
    position's logits (``reference.TOKEN_GAP_LIMIT_STD``'s form).
    ``logits [B, P, V]`` are those of ``forward`` at the positions that
    produced ``outputs [B][P]``."""
    z = np.asarray(logits, np.float32)
    tokens = np.asarray(outputs)
    picked = np.take_along_axis(z, tokens[..., None], axis=-1)[..., 0]
    return (z.max(-1) - picked) / z.std(-1)


# The check scores every SCORE_EVERY-th of a row's generated tokens: the
# first (the prefill program's logits) and the last step of every decode
# window after it.
SCORE_EVERY = 8

# The limits, each between the program's largest reading over its seeds and
# the nearest wrong program's; ``benchmarks/OURO.md`` has every reading (my
# chip runs, PR 48; one v5e chip, the configuration's widths, the cell's own
# check). A limit was set once, from those readings, and is not widened to
# fit a run. A bf16 program read against float32 drifts with depth, and here
# a token runs 192 layers: ``mistral7b``'s 32 layers put its logits 0.05-0.06
# of their standard deviation off (``reference.TOKEN_GAP_LIMIT_STD``'s
# note), these read a last plane 0.35 off and gaps to match, so the limits
# on logits are wide and the planes carry the precision.
#
# Largest gap of the check's 256 scored tokens. Program 0.64-1.19 over eight
# seeds (0.64, 0.67, 0.70, 0.73, 0.79, 0.91, 1.09, 1.19). Pass t on pass 0's
# planes 3.48, three passes for four 5.27, no norm between the passes 6.86;
# int8 pages 1.68 (passes it, as rounding should).
TOKEN_GAP_LIMIT_STD = 2.5
# Mean gap. Program 0.059-0.155 over the eight seeds; pass 0's planes 1.38,
# three passes 3.00, no norm between 4.14; int8 pages 0.23.
MEAN_GAP_LIMIT_STD = 0.5
# Plane 0 (layer 0 of the first pass) in a row's first and last block, the
# median over the rows of the larger of K's and V's error. Program
# 0.00302-0.00306 on every seed (bf16 of a float32 row); int8 pages, one
# scale a token and head, 0.00737: NOT correct, and this limit alone says so.
# Pass 0's planes (written four times over) 1.44.
FIRST_PASS_KV_LIMIT = 0.0045
# Plane T * L - 1 (the last layer of the last pass) likewise. Program
# 0.325-0.361 over seven seeds (bf16 through 191 layers and three final
# norms; plane 144, the first of the last pass, read 0.146 on the seed that
# looked there); int8 pages 0.474; a plane nothing wrote 1.0 (three passes,
# pass 0's planes), no norm between the passes 1.43.
LAST_PASS_KV_LIMIT = 0.7
# ... and the largest row of either plane: program 0.39-0.44, int8 0.52, a
# plane nothing wrote 1.0, a page that is not the row's about 1.4
# (``benchmarks/LFM2.md``).
KV_ROW_LIMIT = 0.8
