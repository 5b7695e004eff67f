"""The plain reference for ``sdar_moe`` (JetLM SDAR-30B-A3B-Chat): the layer
and the block-diffusion loop of ISSUE 54 in straightforward ``jax.numpy``,
weights as stored, everything else float32 under
``jax.default_matmul_precision('highest')``. No cache, no kernel, no
batching: one sequence at a time, every forward over the WHOLE sequence so
far under the block-causal mask, attention a dense masked softmax, every held
expert applied densely, one after the other, with a per-token gate that is
zero where the token did not choose it.

It takes the program's parameter tree (``sdar.init_on_device``'s key names
are all it shares with the code under test; nothing of ``distllm_tpu.models``
or ``distllm_tpu.ops`` is imported) and the configuration file's published
keys, and is given the same share of the experts as the program:
``num_experts`` experts are held, ids ``first_local_expert`` onward of the
``num_routed_experts`` the router ranks; what the absent ones add is left out.

The layer, ``N`` query heads on ``G`` KV heads of ``d`` lanes, block ``B``::

    u = rms(x; g1);  q = u Wq;  k = u Wk;  v = u Wv
    q = rms(q; gq);  k = rms(k; gk)   over each head's d lanes
    q, k = rope(q, k, p)              pairs (i, i + d / 2), theta
    a = softmax(q k^T / sqrt(d) + mask) v,  key j visible iff
        j < (p // B + 1) * B
    x' = x + a Wo;  h = rms(x'; g2);  r = h Wr
    S = the k largest of r;  g_e = softmax over S of r_e
    x_next = x' + sum_{e in S} g_e (silu(h G_e) * (h U_e)) D_e

The loop (``generate``): a prompt's ``P // B`` whole blocks are context; its
last ``P mod B`` tokens are the given positions of the first block, the rest
of it the mask token; ``S`` times a forward of context and block gives each
position of the block a candidate and a confidence, and ``select`` decides
``n_s = B // S + (s < B mod S)`` masked positions, or every one over a
threshold where those are more; then the block is context. ``replay_block``
walks one block's steps from RECORDED decided-at steps and tokens instead, so
that a sampled or a long run of the program can be held to its own logits.

ASSUMED (the configuration file's ``assumed``), as the program does: (1) no
shift: the logits at a position are the distribution of the token AT it; (2)
whether a position is masked is a flag carried by the loop, never ``id ==
mask_token_id`` (the published loop reads it off the ids: a prompt token
that equals the mask id is a token here); (3) a sampled row's confidence is
the candidate's probability under the FILTERED distribution (temperature,
top-k, top-p), a greedy row's the argmax's softmax probability at
temperature 1 over the whole vocabulary; (4) ties in confidence go to the
lower position.

The limits of the cell's check are at the end, each with its reason
(``benchmarks/SDAR.md`` has the readings they lie between).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, cos, sin):
    """``x [T, N, d]`` rotated in pairs ``(i, i + d / 2)``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _angles(model: dict, length: int):
    d = model['head_dim']
    freq = float(model['rope_theta']) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d
    )
    angles = np.arange(length, dtype=np.float64)[:, None] * freq[None, :]
    return jnp.asarray(np.cos(angles), F32), jnp.asarray(np.sin(angles), F32)


def layer_forward(x, lp, model: dict, cos, sin, seen):
    """One layer over ``x [T, H]`` (``lp``: the layer's leaves, float32;
    ``seen [T, T]``: the mask). Returns ``(x_next, k, v)`` with ``k``, ``v``
    ``[T, G, d]`` as a cache would hold them (normed and rotated keys)."""
    eps, d = model['rms_norm_eps'], model['head_dim']
    heads, kv_heads = model['num_attention_heads'], model['num_key_value_heads']
    t = x.shape[0]
    u = _rms(x, lp['attn_ln']['scale'], eps)
    q = (u @ lp['q']['kernel']).reshape(t, heads, d)
    k = (u @ lp['k']['kernel']).reshape(t, kv_heads, d)
    v = (u @ lp['v']['kernel']).reshape(t, kv_heads, d)
    q = _rotate(_rms(q, lp['q_norm']['scale'], eps), cos, sin)
    k = _rotate(_rms(k, lp['k_norm']['scale'], eps), cos, sin)
    k_all = jnp.repeat(k, heads // kv_heads, axis=1)
    v_all = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum('qnd,knd->nqk', q, k_all) / math.sqrt(d)
    scores = jnp.where(seen[None], scores, -1e30)
    a = jnp.einsum('nqk,knd->qnd', jax.nn.softmax(scores, -1), v_all)
    x = x + a.reshape(t, heads * d) @ lp['o']['kernel']
    h = _rms(x, lp['mlp_ln']['scale'], eps)
    return x + experts(h, lp, model), k, v


def experts(h, lp, model: dict):
    """The held experts one after the other, each over every row with the
    row's gate (zero where the row did not choose it)."""
    r = h @ lp['router']['kernel']
    top_r, top_e = jax.lax.top_k(r, model['num_experts_per_tok'])
    gates = jax.nn.softmax(top_r, -1)
    first = model.get('first_local_expert', 0)

    def one_expert(out, xs):
        e, gate, up, down = xs
        g_e = jnp.where(top_e == e, gates, 0.0).sum(-1)  # 0: not chosen
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return out + g_e[:, None] * y, None

    held = lp['gate']['kernel'].shape[0]
    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (first + jnp.arange(held), lp['gate']['kernel'], lp['up']['kernel'],
         lp['down']['kernel']),
    )
    return out


@functools.partial(jax.jit, static_argnames=('model_key', 'keep', 'dtype'))
def _forward(params, ids, length, *, model_key, keep, dtype):
    model = dict(model_key)
    with jax.default_matmul_precision('highest'):
        t = ids.shape[0]
        cos, sin = _angles(model, t)
        j = jnp.arange(t)
        b = model['block_length']
        seen = (j[None, :] < (j[:, None] // b + 1) * b) & (j[None, :] < length)
        x = jnp.asarray(params['embed'])[ids].astype(dtype).astype(F32)

        def layer(x, lp):
            lp = jax.tree.map(lambda a: a.astype(dtype).astype(F32), lp)
            x, k, v = layer_forward(x, lp, model, cos, sin, seen)
            return x, (k, v)

        x, (k, v) = jax.lax.scan(layer, x, params['layers'])
        hidden = _rms(x, params['final_ln']['scale'].astype(F32), model['rms_norm_eps'])
        logits = hidden @ params['head'].astype(dtype).astype(F32)
        kept = {l: (k[l], v[l]) for l in keep}
    return logits, kept


def _key(model: dict):
    names = (
        'head_dim', 'rope_theta', 'rms_norm_eps', 'num_attention_heads',
        'num_key_value_heads', 'num_experts_per_tok', 'first_local_expert',
        'block_length',
    )
    return tuple((n, model[n]) for n in names if n in model)


def forward(params, model: dict, ids, *, width=None, keep=(), dtype='float32'):
    """Logits ``[T, V]`` float32 of ``ids [T]`` under the block-causal mask,
    and ``{layer: (k, v)}`` of the layers ``keep`` names. ``width`` pads the
    sequence on the right (keys past the sequence are masked) so that a few
    shapes are compiled and not one a length. ``dtype`` rounds the weights
    and the embedding's rows to a narrower type first (the wrong-precision
    arm of the cell's calibration); the arithmetic stays float32."""
    ids = np.asarray(ids, np.int32)
    length = len(ids)
    padded = np.zeros((max(width or 0, length),), np.int32)
    padded[:length] = ids
    logits, kept = _forward(
        params, jnp.asarray(padded), jnp.int32(length), model_key=_key(model),
        keep=tuple(keep), dtype=dtype,
    )
    return logits[:length], {l: (k[:length], v[:length]) for l, (k, v) in kept.items()}


def compile_ahead(model: dict, shapes: dict, widths, keep=()):
    """Lower and compile ``forward`` at every width of ``widths`` from the
    parameter tree's ``shapes`` alone. The results are dropped: the compile
    cache keeps them, so a driver can have this done on a thread while the
    engine is built and warmed. Nothing here changes what they compute."""
    sds = jax.ShapeDtypeStruct
    for width in widths:
        _forward.lower(
            shapes, sds((width,), jnp.int32), sds((), jnp.int32),
            model_key=_key(model), keep=tuple(keep), dtype='float32',
        ).compile()


# ------------------------------------------------------------------ the loop
def schedule(block: int, steps: int) -> list[int]:
    """Positions the schedule decides at each of ``steps`` denoise steps."""
    return [block // steps + (s < block % steps) for s in range(steps)]


def confidence(logits, token: int, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0) -> float:
    """The probability ``token`` has at a position with ``logits [V]``: under
    the filtered distribution of a sampled row (temperature, the ``top_k``
    largest with ties, then the smallest nucleus whose mass reaches
    ``top_p``, renormalised), by a descending sort; a greedy row's softmax
    probability at temperature 1 over the whole vocabulary."""
    x = np.asarray(logits, np.float64)
    if temperature <= 0:
        p = np.exp(x - x.max())
        return float(p[token] / p.sum())
    x = x / temperature
    p = np.exp(x - x.max())
    p /= p.sum()
    keep = np.ones_like(p, bool)
    if 0 < top_k < len(x):
        keep &= x >= np.sort(x)[-top_k]
    if top_p < 1.0:
        order = np.argsort(-x, kind='stable')
        sorted_p = np.where(keep[order], p[order], 0.0)
        before = np.cumsum(sorted_p) - sorted_p  # mass strictly above
        nucleus = np.zeros_like(keep)
        nucleus[order] = before < top_p
        # every token tied with the last one kept is kept
        keep &= x >= x[nucleus & keep].min()
    return float(p[token] / p[keep].sum()) if keep[token] else 0.0


def select(conf, masked, count: int, threshold=None):
    """The positions a step decides: among the masked ones the ``count`` of
    highest confidence, ties to the lower position; with a ``threshold``
    every masked one over it where those are more than ``count``."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    chosen = np.zeros_like(masked)
    chosen[order[:count]] = True
    if threshold is not None:
        over = masked & (conf > threshold)
        if over.sum() > count:
            return over
    return chosen


def replay_block(params, model: dict, context, given, tokens, decided_at,
                 steps: int, *, width=None, dtype='float32', keep=()):
    """One block's denoise forwards from what a run RECORDED: ``context`` the
    whole blocks before it, ``given`` its given tokens (a first block's
    prompt remainder), ``tokens [B]`` what it ended as and ``decided_at [B]``
    the step each position was decided at (-1 a given one). Yields, for ``s =
    0 .. steps - 1``, ``(logits [B, V], masked [B])``: the reference's logits
    at the block's positions with the positions decided before ``s`` in
    place, and which positions were still masked."""
    block = model['block_length']
    tokens, decided_at = np.asarray(tokens), np.asarray(decided_at)
    assert len(context) % block == 0 and len(tokens) == block
    assert list(tokens[:len(given)]) == list(given)
    for s in range(steps):
        masked = decided_at >= s
        ids = np.where(masked, model['mask_token_id'], tokens)
        logits, _ = forward(
            params, model, list(context) + list(ids), width=width, dtype=dtype,
            keep=keep,  # a caller that wants one compiled program a width
        )
        yield np.asarray(logits[len(context):]), masked


def generate(params, model: dict, prompt, max_tokens: int, steps: int,
             threshold=None, dtype='float32'):
    """The greedy loop: ``(tokens, decided_at)`` of ``max_tokens`` output
    tokens (the last block decided whole, then cut)."""
    block = model['block_length']
    known = list(prompt)
    first = len(known) // block * block
    out, at_out = [], []
    while len(out) < max_tokens:
        context, given = known[:first], known[first:]
        ids = np.array(given + [model['mask_token_id']] * (block - len(given)))
        masked = np.arange(block) >= len(given)
        at = np.where(masked, steps, -1)
        for s, count in enumerate(schedule(block, steps)):
            logits, _ = forward(params, model, context + list(ids), dtype=dtype)
            logits = np.asarray(logits[first:])
            cand = logits.argmax(-1)
            conf = [confidence(logits[i], cand[i]) for i in range(block)]
            decide = select(conf, masked, count, threshold)
            ids = np.where(decide, cand, ids)
            at = np.where(decide, s, at)
            masked &= ~decide
        out += [int(t) for t in ids[len(given):]]
        at_out += [int(a) for a in at[len(given):]]
        known = context + [int(t) for t in ids]
        first += block
    return out[:max_tokens], at_out[:max_tokens]


# ------------------------------------------------- the cell's check's limits
# Each lies between two readings on the chip at the cell's widths
# (benchmarks/SDAR.md has them all; my chip runs, PR 54): what the program
# read over nine seeds, and what a wrong program read.
#
# A decided token's logit below the reference's largest at its position and
# step, in standard deviations of that position's logits. The program: 0.0 to
# 0.021 (a near-tie that bfloat16 turns over). A prefill under the causal
# mask: 1.54.
TOKEN_GAP_LIMIT_STD = 0.25
# The mean of those gaps over all the scored positions. The program: at most
# 0.00066. A prefill under the causal mask: 0.68.
MEAN_GAP_LIMIT_STD = 0.02
# How far the reference's confidence at the position the program decided lies
# under the reference's most confident masked position, as a share of it, the
# mean over the scored steps. At seeded weights the four positions'
# confidences lie within half a percent of each other, so the program itself
# reads 0.0005 to 0.00155 (bfloat16 turns near-ties over); keeping the LEAST
# confident position reads 0.0034.
CONFIDENCE_LIMIT = 0.0023
# Relative RMS error of layer 0's pages (a function of a token and its
# position alone) against the reference's K and V, the median over the rows.
# The program: 0.00331 to 0.00337 (bfloat16 K and V behind float32 norms).
# Every norm's statistics in bfloat16 where float32 is stated: 0.00386.
KV_CONTENT_LIMIT = 0.0036
# The same of the last layer: behind 47 layers of routed experts. The program:
# 0.0125 to 0.0132. A prefill under the causal mask: 0.81.
KV_LAST_CONTENT_LIMIT = 0.05


def token_gaps(logits, tokens):
    """``(max logit - logit of the token) / std`` a position: ``logits [B,
    V]``, ``tokens [B]``."""
    logits = np.asarray(logits, np.float64)
    chosen = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return (logits.max(-1) - chosen) / logits.std(-1)


def kv_content_error(held, want) -> float:
    """Relative RMS error of pages ``held`` against the reference's rows."""
    held, want = np.asarray(held, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((held - want) ** 2).sum() / (want ** 2).sum()))
