"""Plain references, independent of the code under test: the published
forward passes in straightforward ``jax.numpy``, weights as stored,
activations and accumulation in float32 under
``jax.default_matmul_precision('highest')`` (on a TPU a float32 matmul runs in
lower precision otherwise), no kernel, no cache, no batching tricks.

They take the program's parameter trees as they are (``bert.init``,
``mistral.init_on_device``): the tree's key names are the one thing shared.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, F32), tree)


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p['scale'] + p['bias']


def _lin(x, p):
    return x @ p['kernel'] + p['bias']


# ------------------------------------------------------------------ BERT
def bert_embed(params: dict, model: dict, ids, mask) -> np.ndarray:
    """BERT (post-LN, learned positions, erf GELU) -> interior-token mean
    pooling (the start token and each row's end token left out, as the
    pipeline's mean pooler documents) -> unit rows ``[B, H]`` float32."""
    heads = model['num_attention_heads']
    eps = model['layer_norm_eps']
    ids = jnp.asarray(ids)
    mask = jnp.asarray(mask)
    with jax.default_matmul_precision('highest'):
        p = _f32(params)
        emb = p['embeddings']
        b, s = ids.shape
        x = emb['word'][ids] + emb['position'][None, :s] + emb['token_type'][0]
        x = _layer_norm(x, emb['ln'], eps)
        d = x.shape[-1] // heads
        bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e30)
        for li in range(model['num_hidden_layers']):
            lp = jax.tree.map(lambda a: a[li], p['layers'])

            def split(t):
                return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

            q, k, v = (split(_lin(x, lp[n])) for n in ('q', 'k', 'v'))
            scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d) + bias
            attn = jax.nn.softmax(scores, axis=-1) @ v
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
            x = _layer_norm(x + _lin(attn, lp['o']), lp['attn_ln'], eps)
            mlp = _lin(jax.nn.gelu(_lin(x, lp['up']), approximate=False), lp['down'])
            x = _layer_norm(x + mlp, lp['mlp_ln'], eps)
        pos = jnp.arange(s)[None, :]
        lengths = mask.sum(1, keepdims=True)
        interior = (mask > 0) & (pos != 0) & (pos != lengths - 1)
        w = interior.astype(F32)[..., None]
        pooled = (x * w).sum(1) / jnp.maximum(w.sum(1), 1e-9)
        pooled = pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return np.asarray(pooled, np.float32)


def cosines(a: np.ndarray, b: np.ndarray) -> list[float]:
    a = a / np.linalg.norm(a, axis=1, keepdims=True)
    b = b / np.linalg.norm(b, axis=1, keepdims=True)
    return [float(x) for x in (a * b).sum(1)]


# --------------------------------------------------------------- Mistral
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate ``[B, S, N, D]`` by position, pairing dims ``(i, i + D/2)``
    (the rotate_half layout of the published Mistral code)."""
    d = x.shape[-1]
    s = x.shape[1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    freqs = np.outer(np.arange(s, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(freqs), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(freqs), F32)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mistral_layer(x, lp, heads, kv_heads, head_dim, theta, eps):
    b, s, _ = x.shape
    lp = _f32(lp)
    h = _rms(x, lp['attn_ln']['scale'], eps)
    q = (h @ lp['q']['kernel']).reshape(b, s, heads, head_dim)
    k = (h @ lp['k']['kernel']).reshape(b, s, kv_heads, head_dim)
    v = (h @ lp['v']['kernel']).reshape(b, s, kv_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum('bqnd,bknd->bnqk', q, k) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    attn = jnp.einsum('bnqk,bknd->bqnd', jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, s, heads * head_dim) @ lp['o']['kernel']
    h = _rms(x, lp['mlp_ln']['scale'], eps)
    gated = jax.nn.silu(h @ lp['gate']['kernel']) * (h @ lp['up']['kernel'])
    return x + gated @ lp['down']['kernel']


def mistral_logits(params: dict, model: dict, ids) -> jnp.ndarray:
    """Causal forward of the Mistral block over right-padded ``ids``
    ``[B, S]`` -> float32 logits ``[B, S, V]``. One layer at a time, each
    layer's weights widened to float32 only while it runs, so that it fits
    beside the bf16 weights. Right padding cannot reach an earlier position
    through the causal mask, so no padding mask is needed."""
    heads = model['num_attention_heads']
    kv_heads = model['num_key_value_heads']
    head_dim = model.get('head_dim') or model['hidden_size'] // heads
    theta, eps = model['rope_theta'], model['rms_norm_eps']

    @jax.jit
    def layer(x, lp):
        with jax.default_matmul_precision('highest'):
            return _mistral_layer(x, lp, heads, kv_heads, head_dim, theta, eps)

    @jax.jit
    def head(x, scale, kernel):
        with jax.default_matmul_precision('highest'):
            return _rms(x, scale.astype(F32), eps) @ kernel.astype(F32)

    x = jnp.asarray(params['embed'])[jnp.asarray(ids)].astype(F32)
    for li in range(model['num_hidden_layers']):
        x = layer(x, jax.tree.map(lambda a: a[li], params['layers']))
    kernel = params['lm_head'] if 'lm_head' in params else params['embed'].T
    return head(x, params['final_ln']['scale'], kernel)


# Why 0.85. The engine's token has the engine's largest logit; in the
# reference its logit can fall short of the reference's largest by at most the
# sum of two single-logit differences between the two computations. PR 21
# calibrated two orders of the same bf16 math at these widths on the chip
# (PERF.md section 6): relative RMS difference 0.05-0.06 of the logits'
# standard deviation, largest single difference 3.9-5.2 RMS, limit 7. So the
# gap is bounded by 2 x 7 x 0.06 = 0.84 standard deviations. A uniformly
# random token lies about 4 standard deviations under the largest of 32768
# logits and passes one position with probability about 4e-4.
TOKEN_GAP_LIMIT_STD = 0.85


def token_gaps(logits, prompt_lens, outputs) -> list[float]:
    """For every generated token, how far its reference logit lies under the
    reference's largest at that position, in standard deviations of that
    position's logits. ``logits`` scored prompt + output, teacher-forced."""
    gaps = []
    for row, (n_prompt, tokens) in enumerate(zip(prompt_lens, outputs)):
        rows = np.asarray(
            logits[row, n_prompt - 1: n_prompt - 1 + len(tokens)], np.float32
        )
        for step, token in enumerate(tokens):
            z = rows[step]
            gaps.append(float((z.max() - z[token]) / z.std()))
    return gaps
