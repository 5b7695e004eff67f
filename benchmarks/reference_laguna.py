"""The plain reference for ``laguna`` (poolside Laguna-XS.2): the forward pass
of ISSUE 30's equations in straightforward ``jax.numpy``, weights as stored,
everything else float32 under ``jax.default_matmul_precision('highest')``.
No cache, no kernels, no batching tricks: one row and one layer at a time (so
that it fits beside the bf16 weights), attention as a dense masked softmax
computed a block of queries at a time (so that 8448 tokens fit), the experts
as a loop over the held experts with a per-token weight that is zero where
the token did not choose the expert (no sorting, no grouping).

It takes the program's parameter tree (``laguna.init_on_device``'s key names
are all it shares with the code under test) and the configuration file's
published keys, and is given the same share of the experts and of the
vocabulary as the program: ``num_experts`` experts are held, ids
``first_local_expert`` onward of the ``num_routed_experts`` the router ranks;
what the absent ones would add is left out.

For layer ``l`` of kind ``t``, ``H = num_attention_heads_per_layer[l]``, ``G``
KV heads, ``d = head_dim``::

    h = rms(x);  q = h Wq [H, d];  k = h Wk [G, d];  v = h Wv [G, d]
    q, k = rope_t(q, k, pos)
    a = softmax(q k^T / sqrt(d) + mask_t) v   mask_full causal,
                                              mask_window causal and i - w < j
    g = sigmoid(h Wg) [H];  x = x + (g[:, None] * a) Wo
    h2 = rms(x)
    dense layer:   x = x + (silu(h2 Wg1) * (h2 Wu)) Wd
    sparse layer:  p = softmax(h2 Wr);  S = top_k(p);  w_e = s p_e / sum_S p
                   x = x + sum_{e in S} w_e E_e(h2) + E_shared(h2)

Three things the published config does not settle are set as the family's
sibling ``Laguna-S-2.1`` states them (the configuration file's ``assumed``):
the gate is per head, one scalar a head (ASSUMED 1); the kept weights are
renormalised over the kept ``k`` (ASSUMED 2); the router scores by softmax
over all routed experts (ASSUMED 3).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
_TREE = {'full_attention': 'full', 'sliding_attention': 'window'}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_angles(spec: dict, head_dim: int, positions) -> tuple:
    """``(cos, sin, rotated)`` for one attention kind's ``rope_parameters``
    entry: angles ``[S, rotated / 2]`` of the ``rotated = head_dim *
    partial_rotary_factor`` leading dims. ``default``: ``pos * theta^(-2i /
    rotated)``. ``yarn``: the dims that turn more than ``beta_fast`` times
    over the original context keep that frequency, those that turn fewer
    than ``beta_slow`` times have it divided by ``factor``, a linear ramp
    between the two dims (floor and ceiling) where those counts fall; cos
    and sin are multiplied by ``attention_factor``."""
    rotated = int(head_dim * spec.get('partial_rotary_factor', 1.0))
    theta = float(spec['rope_theta'])
    i = np.arange(0, rotated, 2, dtype=np.float64)
    freq = theta ** (-i / rotated)
    scale = 1.0
    if spec.get('rope_type', 'default') == 'yarn':
        factor = float(spec['factor'])
        original = float(spec['original_max_position_embeddings'])

        def dim_of(turns: float) -> float:
            # the dim whose wavelength fits `turns` times into `original`
            return rotated * math.log(original / (turns * 2 * math.pi)) / (
                2 * math.log(theta)
            )

        low = max(math.floor(dim_of(float(spec['beta_fast']))), 0)
        high = min(math.ceil(dim_of(float(spec['beta_slow']))), rotated - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rotated // 2) - low) / (high - low), 0.0, 1.0)
        freq = freq * (1.0 - ramp) + freq / factor * ramp
        scale = float(spec['attention_factor'])
    angles = np.asarray(positions, np.float64)[:, None] * freq[None, :]
    return (
        jnp.asarray(np.cos(angles) * scale, F32),
        jnp.asarray(np.sin(angles) * scale, F32), rotated,
    )


def _rotate(x, cos, sin, rotated):
    """``x [S, N, d]``: the first ``rotated`` dims rotated in pairs ``(i, i +
    rotated / 2)``, the rest passed through."""
    half = rotated // 2
    x1, x2, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def attention(h, lp, heads, kv_heads, d, rope, window):
    """One row ``h [S, hidden]`` through one attention layer, a block of
    queries at a time; ``window`` None for a full layer."""
    s = h.shape[0]
    q = (h @ lp['q']['kernel']).reshape(s, heads, d)
    k = (h @ lp['k']['kernel']).reshape(s, kv_heads, d)
    v = (h @ lp['v']['kernel']).reshape(s, kv_heads, d)
    q, k = _rotate(q, *rope), _rotate(k, *rope)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    j = jnp.arange(s)
    pad = -s % QUERY_BLOCK
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, heads, d
    )

    def block(args):
        first, q_b = args
        i = first + jnp.arange(QUERY_BLOCK)
        seen = j[None, :] <= i[:, None]  # causal
        if window is not None:
            seen &= j[None, :] > i[:, None] - window
        scores = jnp.einsum('qnd,knd->nqk', q_b, k) / math.sqrt(d)
        scores = jnp.where(seen[None], scores, -1e30)
        return jnp.einsum('nqk,knd->qnd', jax.nn.softmax(scores, -1), v)

    firsts = jnp.arange(q_blocks.shape[0]) * QUERY_BLOCK
    a = jax.lax.map(block, (firsts, q_blocks)).reshape(-1, heads, d)[:s]
    # ASSUMED 1: the gate is one sigmoid a head, of the layer's normed input.
    g = jax.nn.sigmoid(h @ lp['attn_gate']['kernel'])  # [S, H]
    return (a * g[:, :, None]).reshape(s, heads * d) @ lp['o']['kernel']


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def sparse_mlp(h2, mp, k, scale, first_held):
    """Router over every routed expert, the held experts one after the
    other, the shared expert once."""
    p = jax.nn.softmax(h2 @ mp['router']['kernel'], -1)  # ASSUMED 3
    top_p, top_e = jax.lax.top_k(p, k)
    w = scale * top_p / top_p.sum(-1, keepdims=True)  # ASSUMED 2

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
def _programs(d, kv_heads, eps, per_token, routed_scale, first_held):
    """``(layer, head, first_kv)`` jitted once for a model's numbers, so
    that rows of one width and layers of one kind share a compiled program:
    made anew in every call they compiled again for every row, and a
    program with a float32 matmul at the highest precision is 5-14 s of
    compiling on a v5e's host when no compile cache has it."""

    def layer(x, lp, mp, cos, sin, *, heads, rotated, window, sparse):
        with jax.default_matmul_precision('highest'):
            lp, mp = jax.tree.map(lambda a: a.astype(F32), (lp, mp))
            h = _rms(x, lp['ln']['scale'], eps)
            x = x + attention(
                h, lp, heads, kv_heads, d, (cos, sin, rotated), window
            )
            h2 = _rms(x, mp['mlp_ln']['scale'], eps)
            if not sparse:
                return x + _swiglu(
                    h2, mp['gate']['kernel'], mp['up']['kernel'],
                    mp['down']['kernel'],
                )
            return x + sparse_mlp(h2, mp, per_token, routed_scale, first_held)

    def head(x, scale, kernel):
        with jax.default_matmul_precision('highest'):
            return _rms(x, scale.astype(F32), eps) @ kernel.astype(F32)

    def first_kv(x, scale, k_kernel, v_kernel, cos, sin, *, rotated):
        with jax.default_matmul_precision('highest'):
            h = _rms(x.astype(F32), scale.astype(F32), eps)
            k = (h @ k_kernel.astype(F32)).reshape(-1, kv_heads, d)
            v = (h @ v_kernel.astype(F32)).reshape(-1, kv_heads, d)
            return _rotate(k, cos, sin, rotated), v

    return (
        jax.jit(layer, static_argnames=('heads', 'rotated', 'window', 'sparse')),
        jax.jit(head),
        jax.jit(first_kv, static_argnames=('rotated',)),
    )


def _programs_of(model: dict):
    return _programs(
        model['head_dim'], model['num_key_value_heads'], model['rms_norm_eps'],
        model['num_experts_per_tok'], model['moe_routed_scaling_factor'],
        model.get('first_local_expert', 0),
    )


def laguna_logits(params: dict, model: dict, ids, score_at) -> np.ndarray:
    """Causal forward over right-padded ``ids [B, S]`` from no state ->
    float32 logits ``[B, P, V]`` at the positions ``score_at [B, P]`` of
    each row. Right padding cannot reach an earlier position through a
    causal mask, so no padding mask is needed."""
    ids, score_at = np.asarray(ids), np.asarray(score_at)
    d = model['head_dim']
    heads_of = model['num_attention_heads_per_layer']
    positions = np.arange(ids.shape[1])
    ropes = {
        kind: rope_angles(model['rope_parameters'][kind], d, positions)
        for kind in _TREE
    }
    layer, head, _ = _programs_of(model)
    out = []
    for row, at in zip(ids, score_at):
        x = jnp.asarray(params['embed'])[jnp.asarray(row)].astype(F32)
        seen = dict.fromkeys(('full', 'window', 'dense', 'sparse'), 0)
        for li, kind in enumerate(model['layer_types']):
            tree, mlp = _TREE[kind], model['mlp_layer_types'][li]
            ai, mi = seen[tree], seen[mlp]
            seen[tree] += 1
            seen[mlp] += 1
            cos, sin, rotated = ropes[kind]
            x = layer(
                x, jax.tree.map(lambda a: a[ai], params[tree]),
                jax.tree.map(lambda a: a[mi], params[mlp]), cos, sin,
                heads=heads_of[li], rotated=rotated,
                window=model['sliding_window'] if tree == 'window' else None,
                sparse=mlp == 'sparse',
            )
        out.append(np.asarray(
            head(x[jnp.asarray(at)], params['final_ln']['scale'],
                 params['lm_head'])
        ))
    return np.stack(out)


def first_layer_kv(params: dict, model: dict, ids, positions):
    """``(k, v)``, each float32 ``[T, G, d]``: the rows layer 0 writes into
    its K/V pages for tokens ``ids [T]`` at ``positions [T]`` (K rotated).
    Layer 0 reads the embedding alone, so its K and V are a function of a
    token and its position and of nothing the row attended to: the one place
    where the pool's CONTENT can be held to float32 without the program's
    own noise from the layers below."""
    kind = model['layer_types'][0]
    lp = params[_TREE[kind]]
    cos, sin, rotated = rope_angles(
        model['rope_parameters'][kind], model['head_dim'], positions
    )
    k, v = _programs_of(model)[2](
        jnp.asarray(params['embed'])[jnp.asarray(ids)], lp['ln']['scale'][0],
        lp['k']['kernel'][0], lp['v']['kernel'][0], cos, sin, rotated=rotated,
    )
    return np.asarray(k), np.asarray(v)


def compile_ahead(model: dict, shapes: dict, widths, scored: int, kv_rows: int):
    """Lower and compile every program that ``laguna_logits`` (rows padded
    to ``widths``, ``scored`` positions a row) and ``first_layer_kv``
    (``kv_rows`` tokens a call) will call, from the parameter tree's
    ``shapes`` alone. The results are dropped: the compile cache keeps them,
    so a driver can have this done on a thread while the engine is built and
    warmed, and the check then finds its programs compiled. Nothing here
    changes what they compute."""
    sds = jax.ShapeDtypeStruct
    layer, head, first_kv = _programs_of(model)
    hidden, d = shapes['embed'].shape[1], model['head_dim']

    def one(tree):  # a layer of a stacked tree
        return jax.tree.map(lambda a: sds(a.shape[1:], a.dtype), tree)

    def angles(kind, rows):
        spec = model['rope_parameters'][kind]
        rotated = int(d * spec.get('partial_rotary_factor', 1.0))
        return (sds((rows, rotated // 2), F32),) * 2, rotated

    kinds = sorted(set(zip(
        model['layer_types'], model['mlp_layer_types'],
        model['num_attention_heads_per_layer'],
    )))
    for width in widths:
        for kind, mlp, heads in kinds:
            tables, rotated = angles(kind, width)
            layer.lower(
                sds((width, hidden), F32), one(shapes[_TREE[kind]]),
                one(shapes[mlp]), *tables, heads=heads, rotated=rotated,
                window=(
                    model['sliding_window'] if _TREE[kind] == 'window' else None
                ),
                sparse=mlp == 'sparse',
            ).compile()
    head.lower(
        sds((scored, hidden), F32), shapes['final_ln']['scale'],
        shapes['lm_head'],
    ).compile()
    first = model['layer_types'][0]
    lp = one(shapes[_TREE[first]])
    tables, rotated = angles(first, kv_rows)
    first_kv.lower(
        sds((kv_rows, hidden), shapes['embed'].dtype), lp['ln']['scale'],
        lp['k']['kernel'], lp['v']['kernel'], *tables, rotated=rotated,
    ).compile()


def kv_content_error(held, want) -> float:
    """RMS of ``held - want`` over the RMS of ``want``."""
    held, want = np.asarray(held, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((held - want) ** 2).mean() / (want ** 2).mean()))


def token_gaps(logits: np.ndarray, outputs) -> np.ndarray:
    """``[B, P]``: how far each generated token's reference logit lies under
    the reference's largest at its position, in standard deviations of that
    position's logits. ``logits [B, P, V]`` are those of ``laguna_logits``
    at the positions that produced ``outputs [B][P]``."""
    z = np.asarray(logits, np.float32)
    tokens = np.asarray(outputs)
    picked = np.take_along_axis(z, tokens[..., None], axis=-1)[..., 0]
    return (z.max(-1) - picked) / z.std(-1)


# Four limits, calibrated on the chip at the configuration's widths through
# the cell's own check (8 rows x 256 tokens of one greedy call of 48 prompts;
# ``scripts/probe_laguna_reference.py`` builds the engine with each fault; my
# chip runs, PR 30; ``PERF.md`` section 6 has every reading). The bf16
# program's token is the reference's largest logit at most positions and a
# near tie at the rest.
#
# ``ROW_GAP_LIMIT_STD`` 0.3 on the median over the 8 rows of each row's
# LARGEST gap (the program 0.088-0.146 over ten seeds): the routed scale 2.5
# left out reads 0.513 (every row 0.41-0.58), a block freed while a query
# still sees it 1.26, YaRN left out 2.84, the gate left out 3.02, all 128 dims
# rotated in the full layers 3.78. ``TOKEN_GAP_LIMIT_STD`` 0.85 on the largest
# of all 2048 positions (the value and the reasoning of
# ``reference.TOKEN_GAP_LIMIT_STD``; the program 0.15-0.39, an extreme of 256
# near ties a row, which wanders) is for a fault in one row alone, which a
# median would miss: the four gross faults read 3.2-4.3 there.
#
# Neither sees a window a block off: under N(0, 0.02) kernels a score's spread
# is 0.8, the softmax over 512 keys is nearly flat, and 16 keys too few or too
# many move every logit a little and no token far (row median 0.244 and 0.209,
# largest 0.52 and 0.26). ``MEAN_GAP_LIMIT_STD`` 0.0047 on the MEAN gap of all
# 2048 positions does: an average goes with the square of the logits' error
# and wanders less. The program reads 0.0019-0.0032 over twelve seeds (single
# rows 0.0005-0.0056), a window of 496 0.0071, of 528 0.0073: the limit lies
# 1.48 times over the one and 1.51 under the other. (It was 0.004 while the
# program had read 0.0020-0.0023 on five seeds; the seven seeds of the
# refusal round, whose programs XLA fuses otherwise, read 0.0019-0.0032, and
# 1.26 times of room over a mean of eight wandering rows is too little. K and
# V rounded to int8 read 0.0047 here and fail the limit below.)
#
# ``KV_CONTENT_LIMIT`` 0.0045 is the precision limit: layer 0's K and V rows in
# each scored row's first and last block of the pool against
# ``first_layer_kv`` (relative RMS error, the larger of K's and V's, the median
# over the rows). The bf16 program reads 0.00294-0.00296 (every row 0.00289-0.00299:
# three roundings), the nearest precision below, K and V rounded to int8 with
# one scale a token and head, 0.00769 (0.00758-0.00778); a wrong rotation of
# the full layers reads tenths here. Sharper seeded attention was tried
# instead, in emulation, and dropped: at 1.5 times the q and k kernels a wrong
# window stands out no more, at 2 times the bf16 program leaves the reference.
TOKEN_GAP_LIMIT_STD = 0.85
ROW_GAP_LIMIT_STD = 0.3
MEAN_GAP_LIMIT_STD = 0.0047
KV_CONTENT_LIMIT = 0.0045
