"""The plain reference for ``granitemoehybrid`` (Granite 4.0-H): the published
equations in straightforward ``jax.numpy``, weights as stored, everything else
float32 under ``jax.default_matmul_precision('highest')``. The Mamba-2
recurrence is a ``lax.scan`` over time steps (no chunking, no carried cache),
attention is a dense masked softmax, the experts are a loop over the held
experts with a mask (no sorting, no grouping), one layer at a time so that it
fits beside the bf16 weights.

It takes the program's parameter tree (``granite_hybrid.init_on_device``'s
key names are all it shares with the code under test) and the configuration
file's published keys.

    x = E[ids] * embedding_multiplier
    x = x + residual_multiplier * mixer(rms(x))
    x = x + residual_multiplier * (moe(rms(x)) + shared(rms(x)))
    logits = rms(x) @ E^T / logits_scaling
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def attention_mixer(h, lp, model):
    """q, k, v, o without bias, grouped heads, causal, no rotation, scores
    times ``attention_multiplier``."""
    b, s, _ = h.shape
    heads, kv_heads = model['num_attention_heads'], model['num_key_value_heads']
    d = model['hidden_size'] // heads
    q = (h @ lp['q']['kernel']).reshape(b, s, heads, d)
    k = (h @ lp['k']['kernel']).reshape(b, s, kv_heads, d)
    v = (h @ lp['v']['kernel']).reshape(b, s, kv_heads, d)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum('bqnd,bknd->bnqk', q, k) * model['attention_multiplier']
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    attn = jnp.einsum('bnqk,bknd->bqnd', jax.nn.softmax(scores, -1), v)
    return attn.reshape(b, s, heads * d) @ lp['o']['kernel']


def ssm_steps(x, dt, a, b_in, c_in, d_skip, lengths=None):
    """The recurrence, one time step at a time from zero state:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``.
    ``x [B, S, H, P]``, ``dt [B, S, H]``, ``a``, ``d_skip [H]``, ``b_in``,
    ``c_in [B, S, N]`` -> ``y [B, S, H, P]`` and the state ``[B, H, P, N]``
    after each row's first ``lengths [B]`` steps (after the last step where
    ``lengths`` is None)."""
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    if lengths is None:
        lengths = jnp.full((bsz,), s)

    def step(carry, xs):
        state, kept = carry
        t, x_t, dt_t, b_t, c_t = xs  # [], [B, H, P], [B, H], [B, N], [B, N]
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = state * decay + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        y_t = (state * c_t[:, None, None, :]).sum(-1) + d_skip[:, None] * x_t
        kept = jnp.where((t < lengths)[:, None, None, None], state, kept)
        return (state, kept), y_t

    swap = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    zeros = jnp.zeros((bsz, h, p, n), F32)
    (_, kept), y = jax.lax.scan(
        step, (zeros, zeros),
        (jnp.arange(s), swap(x), swap(dt), swap(b_in), swap(c_in)),
    )
    return swap(y), kept


def mamba_mixer(h, lp, model, lengths=None):
    """The mixer's output and its SSM state after ``lengths`` positions."""
    b, s, _ = h.shape
    heads, p = model['mamba_n_heads'], model['mamba_d_head']
    n, k = model['mamba_d_state'], model['mamba_d_conv']
    d_inner = heads * p
    conv_dim = d_inner + 2 * n
    proj = h @ lp['in_proj']['kernel']
    z, xbc, dt = (
        proj[..., :d_inner], proj[..., d_inner:d_inner + conv_dim],
        proj[..., d_inner + conv_dim:],
    )
    # Causal depthwise convolution: position t sees t - (k - 1) .. t.
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(lp['conv'][j] * padded[:, j:j + s] for j in range(k))
    xbc = jax.nn.silu(conv + lp['conv_bias'])
    x = xbc[..., :d_inner].reshape(b, s, heads, p)
    b_in, c_in = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt + lp['dt_bias'])
    y, state = ssm_steps(
        x, dt, -jnp.exp(lp['A_log']), b_in, c_in, lp['D'], lengths
    )
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)  # gate first, then the norm
    out = _rms(y, lp['norm']['scale'], model['rms_norm_eps']) @ lp['out_proj']['kernel']
    return out, state


def routed_experts(h, lp, model, first_expert: int):
    """``sum_e g_e expert_e(h)`` over the experts in ``lp`` (ids
    ``first_expert`` onward) that are among a token's top-k of all the
    router's experts; ``g`` is the softmax over those k logits."""
    logits = h @ lp['router']['kernel']  # [..., E_routed]
    top_logits, top_idx = jax.lax.top_k(logits, model['num_experts_per_tok'])
    gates = jax.nn.softmax(top_logits, axis=-1)
    out = jnp.zeros_like(h)
    for e in range(lp['gate']['kernel'].shape[0]):
        g = jnp.where(top_idx == first_expert + e, gates, 0.0).sum(-1)
        hidden = jax.nn.silu(h @ lp['gate']['kernel'][e]) * (h @ lp['up']['kernel'][e])
        out = out + g[..., None] * (hidden @ lp['down']['kernel'][e])
    return out


def shared_mlp(h, lp):
    hidden = jax.nn.silu(h @ lp['shared_gate']['kernel']) * (h @ lp['shared_up']['kernel'])
    return hidden @ lp['shared_down']['kernel']


def layer(x, lp, model, kind: str, lengths=None):
    """One layer in float32; ``lp`` is that layer's slice of its kind's
    tree, as stored. Returns the layer's output and, for a Mamba layer, its
    SSM state after ``lengths`` positions (None for an attention layer)."""
    lp = _f32(lp)
    eps, res = model['rms_norm_eps'], model['residual_multiplier']
    normed = _rms(x, lp['ln']['scale'], eps)
    if kind == 'mamba':
        mixed, state = mamba_mixer(normed, lp, model, lengths)
    else:
        mixed, state = attention_mixer(normed, lp, model), None
    x = x + res * mixed
    h = _rms(x, lp['mlp_ln']['scale'], eps)
    first = model.get('first_local_expert', 0)
    x = x + res * (routed_experts(h, lp, model, first) + shared_mlp(h, lp))
    return x, state


def granite_forward(params: dict, model: dict, ids, lengths=None):
    """Causal forward over right-padded ``ids [B, S]`` from zero state ->
    float32 logits ``[B, S, V]`` and, per Mamba layer, the SSM state ``[B,
    heads, P, N]`` after each row's first ``lengths [B]`` positions (all of
    them where None). Right padding cannot reach an earlier position
    through the causal mask, the causal convolution or the recurrence, so
    the logits need no padding mask."""
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)

    def run(kind):
        @jax.jit
        def f(x, lp):
            with jax.default_matmul_precision('highest'):
                return layer(x, lp, model, kind, lengths)
        return f

    run_kind = {'mamba': run('mamba'), 'attention': run('attention')}

    @jax.jit
    def head(x, scale, embed):
        with jax.default_matmul_precision('highest'):
            h = _rms(x, scale.astype(F32), model['rms_norm_eps'])
            return h @ embed.astype(F32).T / model['logits_scaling']

    x = jnp.asarray(params['embed'])[jnp.asarray(ids)].astype(F32)
    x = x * model['embedding_multiplier']
    seen = {'mamba': 0, 'attention': 0}
    states = []
    for kind in model['layer_types']:
        i = seen[kind]
        seen[kind] += 1
        x, state = run_kind[kind](x, jax.tree.map(lambda a: a[i], params[kind]))
        if state is not None:
            states.append(state)
    return head(x, params['final_ln']['scale'], params['embed']), states


def granite_logits(params: dict, model: dict, ids) -> jnp.ndarray:
    return granite_forward(params, model, ids)[0]


def state_errors(got: list, want: list) -> list[float]:
    """For each Mamba layer, the norm of (``got`` - ``want``) over the norm
    of ``want``: SSM states ``[B, heads, P, N]`` of the same rows."""
    return [
        float(jnp.linalg.norm(jnp.asarray(g, F32) - w) / jnp.linalg.norm(w))
        for g, w in zip(got, want)
    ]


def slow_heads(dt_bias, a_log, share: int = 8):
    """The ``1 / share`` of a Mamba layer's heads whose state decays least
    a step (``softplus(dt_bias) * exp(A_log)`` smallest): what one step's
    rounding adds to their state is still there hundreds of steps later."""
    rate = jax.nn.softplus(jnp.asarray(dt_bias, F32)) * jnp.exp(jnp.asarray(a_log, F32))
    return jnp.argsort(rate)[: rate.shape[0] // share]


def slow_head_state_error(got, want, dt_bias, a_log) -> float:
    """``state_errors`` of one layer over its ``slow_heads`` alone."""
    heads = slow_heads(dt_bias, a_log)
    got, want = jnp.asarray(got, F32)[:, heads], want[:, heads]
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# Why 0.2, and not ``reference.TOKEN_GAP_LIMIT_STD``'s 0.85. The engine's token
# has the engine's largest logit; in the reference it can fall short of the
# reference's largest by at most the difference of two single-logit errors.
# Calibrated on the chip at this configuration's widths (PR 26,
# ``scripts/probe_granite_reference.py``, seeds 3100000019 and 3100000037, 8
# prompts of 48-700 tokens x 16 greedy tokens = 128 positions a seed, prefill
# in 512-token spans and decode through the state pool, against this file):
#
#   as served            rel. RMS of logits 0.026 mean, 0.041 / 0.051 largest;
#                        largest single difference 5.3 RMS; 8 / 6 tokens of 128
#                        differ from the reference's; largest gap 0.046 / 0.023
#   score scale 1/sqrt(128) instead of attention_multiplier
#                        rel. RMS 0.122 mean; 34 / 34 tokens differ; largest
#                        gaps 0.449 / 0.284, 6 and 3 positions over 0.2
#   SSM state rounded to bfloat16 after every span and step
#                        rel. RMS 0.025 mean; largest gap 0.057 / 0.081: NOT told
#                        from the program, whose bf16 weights and activations
#                        make all of its error
#
# PR 21's bound (2 x the largest single difference) is 2 x 5.3 x 0.05 = 0.53
# here and would pass the wrong score scale, so the limit is set from the two
# readings instead: 0.2 is 4.3 times the program's largest gap and under the
# wrong scale's smaller reading (0.284). With top-two gaps of 50176 logits
# spread like an exponential of mean 0.215 and an error difference of 0.037 to
# 0.07 standard deviations, a run of 128 positions passes 0.2 wrongly about
# once in a thousand; at 0.15 it would be once in seventy. A uniformly random
# token lies 4 standard deviations under the largest.
TOKEN_GAP_LIMIT_STD = 0.2


# The state limits (review round of PR 26). The check's rows leave their SSM
# state in the engine's pool; the reference computes the same state from the
# same tokens. Calibrated on the chip at this configuration's widths
# (``scripts/probe_granite_reference.py check``: the cell's own check, 96
# greedy rows of 128-1024 prompt tokens for 128 tokens, 8 of them scored; my
# chip runs, PR 26, seeds 8100000007 and 8200000011), per Mamba layer 0..8:
#
#   as served            0.0047 0.0096 0.0136 0.0168 0.0224 0.0262 0.0273 0.0300 0.0317
#                        0.0044 0.0097 0.0128 0.0176 0.0231 0.0243 0.0252 0.0275 0.0314
#   score scale 1/sqrt(128) (the attention layer sits after Mamba layer 4)
#                        0.0047 0.0100 0.0139 0.0167 0.0239 0.0756 0.0820 0.0954 0.1084
#   state pool in bfloat16 (the precision below the one the module states)
#                        0.0072 0.0135 0.0192 0.0227 0.0274 0.0330 0.0349 0.0406 0.0417
#                        0.0071 0.0128 0.0178 0.0209 0.0284 0.0305 0.0290 0.0342 0.0392
#
# The program's own error is its bfloat16 weights and activations: it grows
# with every layer above, so a bfloat16 pool adds little to a deep layer's
# (0.042 against 0.032). SSM_STATE_LIMIT bounds the largest layer's error
# and is for faults of the math anywhere under a Mamba layer: 0.06 lies 1.9
# times over the program's largest (0.0317) and 1.8 times under the wrong
# score scale's (0.108); with the check's tokens spread over 128 steps that
# fault reads a token gap of 0.189, under TOKEN_GAP_LIMIT_STD, and is caught
# here. SSM_SLOW_HEADS_LIMIT is for the state's own precision. It reads the
# FIRST Mamba layer, whose input is the embedding on both sides, so its error
# is the mixer's own, and of that layer the eighth of the heads whose state
# decays least a step (``slow_heads``): an error made when the state is
# stored stays in those for hundreds of steps, while the error of the inputs
# is no larger there than elsewhere. Slowest 16 of 128 heads: as served
# 0.00347 (seed 8200000011) and, in the cell's own runs from the committed
# files on four more seeds, 0.00385, 0.00313, 0.00369, 0.00288; bfloat16 pool
# 0.01318 (seed 8200000011) and 0.01822 (seed 9500000039); over all heads of
# that layer only 0.0044 against 0.0071. 0.0067 is 1.7 times over the
# program's largest and 2.0 times under the control's smallest. Over the same
# six runs the program's largest layer read at most 0.0351 (1.7 times under
# SSM_STATE_LIMIT) and its largest token gap 0.063.
SSM_STATE_LIMIT = 0.06
SSM_SLOW_HEADS_LIMIT = 0.0067
