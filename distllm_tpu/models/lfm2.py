"""LFM2 mixture-of-experts decoders (``model_type lfm2_moe``: LiquidAI
LFM2-8B-A1B): gated short convolutions in most layers, grouped-query
attention with QK-norm in a few, a dense SwiGLU MLP in the leading layers
and, in the others, routed SwiGLU experts scored by sigmoid with a selection
bias and no shared expert; the output head is the embedding.

``cfg.layer_types`` says which mixer a layer has and is not periodic, so the
serving programs walk the layers unrolled and call one jitted function a
KIND of layer (``(mixer, mlp)``: three kinds as published), which is traced
and lowered once (``common.once_a_kind``).

A sequence holds two things. For every attention layer K/V pages, one
full-context paged group over ``cfg.num_paged_layers`` layers whose rows are
``num_kv_heads * 64`` lanes (``ops/paged_attention.py`` reads heads of half a
lane tile two to a tile). For every conv layer the last ``conv_L_cache - 1``
inputs of its convolution, ``[2, hidden]`` in the model's dtype
(``cfg.state_spec()``): the state pool is a tuple of one ``[slots, 2,
hidden]`` buffer a conv layer, which the decode window rewrites whole and in
place and prefill gathers and scatters by slot.

A layer ``l`` on ``x [T, hidden]`` (transformers ``models/lfm2_moe``)::

    h = rms(x; operator_norm)
    conv:  [B | C | X] = h W_in;  u = B * X
           v_t = sum_j w[j] * u_{t - (K-1) + j}   depthwise, causal, no bias
           out = (C * v) W_out                    state: u_{t-1}, u_{t-2}
    attn:  q, k, v = h Wq, h Wk, h Wv;  q, k = rms over the head's dims
           (q_layernorm, k_layernorm) BEFORE rope(all dims, rotate-half)
           out = softmax(q k^T / sqrt(d)) v Wo    causal, grouped queries
    x = x + out;  h2 = rms(x; ffn_norm)
    dense:  x = x + (silu(h2 W1) * (h2 W3)) W2
    sparse: s = sigmoid(h2 Wr) (float32);  S = top_k(s + expert_bias)
            g_e = routed_scaling_factor * s_e / (sum_S s + 1e-6)
            x = x + sum_{e in S} g_e E_e(h2)
    logits = rms(x; embedding_norm) E^T

The routed experts are ``models/moe.py``: a chip may hold a share of them
(``first_local_expert``, ``num_local_experts``) while the router ranks all
``num_experts``. ``u`` is rounded to the model's dtype before the taps read
it, in a span and in a step alike, so a span that starts from the state reads
what a longer span would have read in place. There is no ``params_from_hf``
yet.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from distllm_tpu.models import common
from distllm_tpu.models.moe import routed_experts
from distllm_tpu.utils import BaseConfig

F32 = jnp.float32
_BANKS = ('gate', 'up', 'down')
_TREES = ('conv', 'attn', 'dense', 'sparse')
_SCALES = ('ln', 'q_ln', 'k_ln', 'mlp_ln')  # {'scale': ...}
# The published normaliser of the kept scores (``norm_topk_prob``).
ROUTER_EPS = 1e-6


class Lfm2MoeConfig(BaseConfig):
    name: Literal['lfm2_moe'] = 'lfm2_moe'
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: tuple[Literal['conv', 'full_attention'], ...] = ('conv',)
    num_heads: int = 32
    num_kv_heads: int = 8
    conv_L_cache: int = 3  # taps of the short convolution
    intermediate_size: int = 7168  # width of a dense layer's MLP
    moe_intermediate_size: int = 1792  # width of one routed expert
    num_dense_layers: int = 2  # the leading layers with a dense MLP
    # The router ranks num_experts; this chip holds num_local_experts of
    # them, ids first_local_expert onward (all of them by default).
    num_experts: int = 32
    num_local_experts: int = 32
    first_local_expert: int = 0
    experts_per_token: int = 4
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    dtype: str = 'bfloat16'

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def num_paged_layers(self) -> int:
        """Layers that own KV pages: the attention layers."""
        return self.layer_types.count('full_attention')

    def count(self, kind: str) -> int:
        """Layers of a parameter tree."""
        dense = min(self.num_dense_layers, self.num_layers)
        return {
            'conv': self.layer_types.count('conv'),
            'attn': self.num_paged_layers,
            'dense': dense, 'sparse': self.num_layers - dense,
        }[kind]

    def layer_indices(self) -> list[tuple[str, int, str, int]]:
        """``(mixer, index in the mixer's tree, MLP kind, index in the
        MLP's tree)`` of every layer, in order."""
        out, seen = [], {'conv': 0, 'attn': 0}
        dense = self.count('dense')
        for li, layer_type in enumerate(self.layer_types):
            mixer = 'conv' if layer_type == 'conv' else 'attn'
            mlp = ('dense', li) if li < dense else ('sparse', li - dense)
            out.append((mixer, seen[mixer], *mlp))
            seen[mixer] += 1
        return out

    def state_spec(self) -> dict:
        """What one sequence holds beside its KV pages: per conv layer the
        last ``conv_L_cache - 1`` inputs of the convolution, in the model's
        dtype (one kind of leaf: there is no float32 state here)."""
        conv = jax.ShapeDtypeStruct(
            (self.conv_L_cache - 1, self.hidden_size), jnp.dtype(self.dtype)
        )
        return {'conv': (conv,) * self.count('conv')}

    def cache_spec(self) -> common.CacheSpec:
        """One full-context K/V group over the attention layers, the conv
        layers' state beside it (they hold no pages), this module's
        programs and no dense prefill: one family of programs carries the
        state from span to span."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_paged_layers),),
            state=self.state_spec(),
            programs=__name__,
            program_prefix='lfm2_',
            dense_prefill=False,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'Lfm2MoeConfig':
        """The published keys as they are, plus two that state a chip's
        share as the other expert families' configurations do
        (``num_routed_experts``: the router's width where ``num_experts``
        counts the experts held; ``first_local_expert``). Values this module
        does not implement are refused by name."""
        refusals = (
            ('conv_bias', bool(hf.get('conv_bias', False)),
             'biases on the conv layers\' projections and taps'),
            ('norm_topk_prob', not hf.get('norm_topk_prob', True),
             'kept scores left unnormalised'),
            ('use_expert_bias', not hf.get('use_expert_bias', True),
             'a router without its selection bias'),
            ('conv_L_cache', hf.get('conv_L_cache', 3) < 2,
             'a convolution that carries nothing from token to token'),
            ('rope_scaling', hf.get('rope_scaling') is not None,
             'a scaled rotation'),
            ('tie_word_embeddings', not hf.get('tie_word_embeddings', True),
             'an output head of its own'),
        )
        for key, refused, what in refusals:
            if refused:
                raise ValueError(
                    f'lfm2_moe: {key}={hf.get(key)!r} is not implemented '
                    f'({what})'
                )
        held = hf['num_experts']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            layer_types=tuple(hf['layer_types']),
            num_heads=hf['num_attention_heads'],
            num_kv_heads=hf.get('num_key_value_heads', hf['num_attention_heads']),
            conv_L_cache=hf.get('conv_L_cache', 3),
            intermediate_size=hf['intermediate_size'],
            moe_intermediate_size=hf['moe_intermediate_size'],
            num_dense_layers=hf.get('num_dense_layers', 0),
            num_experts=hf.get('num_routed_experts', held),
            num_local_experts=held,
            first_local_expert=hf.get('first_local_expert', 0),
            experts_per_token=hf['num_experts_per_tok'],
            routed_scaling_factor=float(hf.get('routed_scaling_factor', 1.0)),
            rope_theta=float(hf.get('rope_theta', 1e6)),
            norm_eps=hf.get('norm_eps', 1e-5),
            max_position_embeddings=hf.get('max_position_embeddings', 128000),
        )


# ------------------------------------------------------------- parameters
def _tree_shapes(cfg: Lfm2MoeConfig, kind: str) -> dict:
    """``name -> shape`` of one layer's parameters in the tree ``kind``
    (kernels ``[in, out]``)."""
    h = cfg.hidden_size
    if kind == 'conv':
        return {
            'ln': (h,), 'in_proj': (h, 3 * h), 'conv': (cfg.conv_L_cache, h),
            'out_proj': (h, h),
        }
    if kind == 'attn':
        d = cfg.head_size
        return {
            'ln': (h,), 'q': (h, cfg.num_heads * d),
            'k': (h, cfg.num_kv_heads * d), 'v': (h, cfg.num_kv_heads * d),
            'q_ln': (d,), 'k_ln': (d,), 'o': (cfg.num_heads * d, h),
        }
    if kind == 'dense':
        i = cfg.intermediate_size
        return {'mlp_ln': (h,), 'gate': (h, i), 'up': (h, i), 'down': (i, h)}
    i, e = cfg.moe_intermediate_size, cfg.num_local_experts
    return {
        'mlp_ln': (h,), 'router': (h, cfg.num_experts),
        'router_bias': (cfg.num_experts,),  # expert_bias, float32
        'gate': (e, h, i), 'up': (e, h, i), 'down': (e, i, h),
    }


def _wrap(name: str, leaf):
    """``{'scale'}`` norms, ``{'taps'}`` the convolution's ``[K, hidden]``
    weights (tap ``j`` multiplies ``u_{t - (K-1) + j}``), ``{'bias'}`` the
    router's selection bias, ``{'kernel'}`` the rest."""
    if name in _SCALES:
        return {'scale': leaf}
    return {{'conv': 'taps', 'router_bias': 'bias'}.get(name, 'kernel'): leaf}


def _trees(cfg: Lfm2MoeConfig) -> dict:
    return common.tree_table(
        _TREES, cfg.count, lambda kind: _tree_shapes(cfg, kind)
    )


def _top_shapes(cfg: Lfm2MoeConfig) -> dict:
    return {'embed': (cfg.vocab_size, cfg.hidden_size)}


def init_on_device(rng: jax.Array, cfg: Lfm2MoeConfig) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels and embedding, unit norm scales, taps normal(0, 1 /
    sqrt(K)) (so that the convolution's output has its input's size), the
    router's selection bias normal(0, 0.05) in float32 (a zero buffer in the
    published code before training; at 0.05 it changes the kept set of most
    tokens), one RNG call per parameter kind."""

    def leaf(name, key, shape, normal):
        if name == 'conv':
            return normal(key, shape, cfg.conv_L_cache ** -0.5)
        if name == 'router_bias':
            return normal(key, shape, 0.05, F32)
        return None

    return common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size, _top_shapes(cfg), _trees(cfg), _wrap,
        _SCALES, leaf,
    )


def param_specs(cfg: Lfm2MoeConfig, params: dict | None = None) -> dict:
    """Expert banks over ``expert``, everything else replicated."""
    return common.tree_specs(
        _top_shapes(cfg), _trees(cfg), _wrap, [('sparse', n) for n in _BANKS]
    )


def params_from_hf(state: dict, cfg: Lfm2MoeConfig) -> dict:
    raise NotImplementedError(
        'lfm2_moe: no converter from a published checkpoint yet (it has to '
        'stack the experts\' w1/w3/w2 into banks, transpose the depthwise '
        'conv weight [C, 1, K] to taps [K, C] and split the layers into '
        'their four trees); serve seeded weights (init_on_device)'
    )


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.norm_eps)


def _rope_tables(cfg: Lfm2MoeConfig, max_len: int):
    cos, sin = common.rope_frequencies(cfg.head_size, max_len, cfg.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def _gated_inputs(h, lp):
    """``(u, C)`` of normed inputs ``h [..., hidden]``: the convolution's
    input ``u = B * X`` rounded to the model's dtype (what the state holds)
    and the output gate."""
    proj = common.dense(h, lp['in_proj']['kernel'])
    b_gate, c_gate, x = jnp.split(proj, 3, axis=-1)
    return (b_gate.astype(F32) * x.astype(F32)).astype(h.dtype), c_gate


def _taps(window, lp, s: int):
    """``v_t = sum_j w[j] * window[t + j]`` for ``t < s``: ``window [..., K -
    1 + s, hidden]`` is the carried inputs, then the span's own. float32."""
    w = lp['conv']['taps'].astype(F32)
    win = window.astype(F32)
    return sum(
        w[j] * jax.lax.slice_in_dim(win, j, j + s, axis=-2)
        for j in range(w.shape[0])
    )


def conv_span(h, lp, conv0, tail_lens):  # distlint: traced
    """A gated short convolution over a span ``h [B, S, hidden]`` that
    starts from ``conv0 [B, K - 1, hidden]`` and counts the first
    ``tail_lens [B]`` positions of each row. Returns the output and the
    state after each row's last counted position: a row with fewer than ``K
    - 1`` new tokens keeps rows of the old state."""
    s = h.shape[1]
    u, c_gate = _gated_inputs(h, lp)
    window = jnp.concatenate([conv0.astype(u.dtype), u], axis=1)
    y = (c_gate.astype(F32) * _taps(window, lp, s)).astype(h.dtype)
    # what the next span starts from (a short row keeps carried rows)
    conv = common.conv_tail(window, tail_lens, conv0.shape[1])
    return common.dense(y, lp['out_proj']['kernel']), conv.astype(conv0.dtype)


def conv_step(h, lp, conv0, live):  # distlint: traced
    """One token of every row, ``h [B, hidden]``; rows that are not
    ``live`` keep their state."""
    u, c_gate = _gated_inputs(h, lp)
    window = jnp.concatenate([conv0.astype(u.dtype), u[:, None]], axis=1)
    y = (c_gate.astype(F32) * _taps(window, lp, 1)[:, 0]).astype(h.dtype)
    conv = jnp.where(live[:, None, None], window[:, 1:].astype(conv0.dtype), conv0)
    return common.dense(y, lp['out_proj']['kernel']), conv


def _qkv(normed, lp, cfg, cos, sin, positions):
    """``normed [B, S, hidden]`` -> ``q [B, S, H, d]``, ``k``, ``v [B, S,
    H_kv, d]``: QK-norm over a head's dims, then the rotation."""
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_size)  # noqa: E731
    q = heads(common.dense(normed, lp['q']['kernel']), cfg.num_heads)
    k = heads(common.dense(normed, lp['k']['kernel']), cfg.num_kv_heads)
    v = heads(common.dense(normed, lp['v']['kernel']), cfg.num_kv_heads)
    q = common.apply_rope(_norm(q, lp['q_ln']['scale'], cfg), cos, sin, positions)
    k = common.apply_rope(_norm(k, lp['k_ln']['scale'], cfg), cos, sin, positions)
    return q, k, v


def _attn_out(attn, lp, cfg):
    return common.dense(
        attn.reshape(*attn.shape[:-2], cfg.num_heads * cfg.head_size),
        lp['o']['kernel'],
    )


def _mlp(x, mp, cfg, mlp_kind, counted, banks, mi):
    """The MLP block of one layer for ``x [T, hidden]`` (already normed);
    returns it and the layer's (routed, held) pair counts. ``banks`` is the
    sparse tree: the expert banks stay stacked, ``mi`` picks the layer
    inside the expert matmuls (``models/moe.py``). No shared expert."""
    if mlp_kind == 'dense':
        return common.dense_mlp(x, mp), jnp.zeros((2,), jnp.int32)
    return routed_experts(
        x, mp['router']['kernel'], *(banks[n]['kernel'] for n in _BANKS),
        cfg.experts_per_token, first_expert=cfg.first_local_expert,
        counted=counted, layer=mi, routed_scale=cfg.routed_scaling_factor,
        scoring='sigmoid', select_bias=mp['router_bias']['bias'],
        norm_eps=ROUTER_EPS,
    )


def _finish_layer(x, mixed, mp, cfg, mlp_kind, counted, banks, mi):
    """Residual of the mixer's output, then the MLP block."""
    return common.finish_layer(
        x, mixed, mp, cfg.norm_eps,
        lambda rows, of_rows: _mlp(rows, mp, cfg, mlp_kind, of_rows, banks, mi),
        counted,
    )


def logits(params: dict, cfg: Lfm2MoeConfig, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; the head is the embedding."""
    return common.dense(hidden, jnp.asarray(params['embed']).T).astype(F32)


def _layer_params(params, mixer, xi, mlp_kind, mi):
    return (
        common.layer_at(params[mixer], xi),
        common.layer_at(
            params[mlp_kind], mi, skip=_BANKS if mlp_kind == 'sparse' else ()
        ),
    )


def _layer_fns(layers: dict, cfg: Lfm2MoeConfig) -> dict:
    """``(mixer, MLP kind) -> layers[mixer](mlp_kind, *arrays)``, jitted
    once a kind of layer."""
    return common.once_a_kind(
        lambda mixer, *rest: layers[mixer](*rest),
        [(mixer, mlp) for mixer, _, mlp, _ in cfg.layer_indices()],
        'lfm2_{}_{}_layer',
    )


# ----------------------------------------------------------------- forwards
def apply(  # distlint: traced
    params: dict,
    cfg: Lfm2MoeConfig,
    input_ids: jnp.ndarray,  # [B, S], right-padded
    attention_mask: jnp.ndarray,  # [B, S]
) -> jnp.ndarray:
    """Dense causal forward from zero state: ``[B, S]`` -> final-normed
    hidden states ``[B, S, hidden]``. No cache: attention is
    ``common.sdpa`` over the span."""
    b, s = input_ids.shape
    tail_lens = attention_mask.astype(jnp.int32).sum(axis=1)
    valid = attention_mask.astype(bool)
    mask = common.causal_mask(s, s)[None, None] & valid[:, None, None, :]
    cos, sin = _rope_tables(cfg, s)
    x = common.embed(params, cfg.dtype, input_ids)
    conv0 = jnp.zeros((b, cfg.conv_L_cache - 1, cfg.hidden_size), x.dtype)
    for mixer, xi, mlp_kind, mi in cfg.layer_indices():
        lp, mp = _layer_params(params, mixer, xi, mlp_kind, mi)
        normed = _norm(x, lp['ln']['scale'], cfg)
        if mixer == 'conv':
            with jax.named_scope('distllm.conv_prefill'):
                mixed, _ = conv_span(normed, lp, conv0, tail_lens)
        else:
            q, k, v = _qkv(normed, lp, cfg, cos, sin, None)
            mixed = _attn_out(common.sdpa(q, k, v, mask=mask), lp, cfg)
        x, _ = _finish_layer(
            x, mixed, mp, cfg, mlp_kind, valid, params.get('sparse'),
            jnp.int32(mi),
        )
    return _norm(x, params['final_ln']['scale'], cfg)


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: Lfm2MoeConfig,
    input_ids: jnp.ndarray,  # [B, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache: jnp.ndarray,  # [L_attn, num_blocks, block_size, N_kv * d]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    state: dict,  # the state pool: per conv layer [slots, K - 1, hidden]
    slots: jnp.ndarray,  # [B] each row's slot (past the pool = pad row)
    max_table_positions: int | None = None,
    attn_backend: str = 'xla',
):
    """One span of every row through the paged path: a whole prompt, or
    one chunk of a long one with the state of the chunk before it. A span
    that starts at position 0 starts from zero state, whatever its slot
    held: that is how a slot is zeroed when a sequence takes it. Returns
    ``(last_logits [B, V] float32, k_cache, v_cache, state)``."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention,
        write_chunk_kv,
    )

    s = input_ids.shape[1]
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    fresh = positions[:, 0] == 0
    cos, sin = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    convs = list(state['conv'])
    x = common.embed(params, cfg.dtype, input_ids)

    def conv_layer(mlp_kind, x, lp, mp, banks, mi, pool, slots, fresh,
                   tail_lens, valid):
        normed = _norm(x, lp['ln']['scale'], cfg)
        with jax.named_scope('distllm.conv_prefill'):
            conv0 = jnp.where(fresh[:, None, None], 0, pool[slots])
            mixed, conv = conv_span(normed, lp, conv0, tail_lens)
            # a pad row's slot lies past the pool: its write is dropped
            pool = pool.at[slots].set(conv, mode='drop')
        x, _ = _finish_layer(x, mixed, mp, cfg, mlp_kind, valid, banks, mi)
        return x, pool

    def attn_layer(mlp_kind, x, lp, mp, banks, mi, k_cache, v_cache, li,
                   table, cos, sin, positions, valid, context_lens,
                   tail_lens):
        normed = _norm(x, lp['ln']['scale'], cfg)
        with jax.named_scope('distllm.attn_full'):
            q, k, v = _qkv(normed, lp, cfg, cos, sin, positions)
            # the stacked pools whole, with the layer whose pages are meant
            k_cache, v_cache = write_chunk_kv(
                k_cache, v_cache, k, v, table, positions, valid, layer=li
            )
            attn = ragged_paged_attention(
                q, k_cache, v_cache, table, context_lens, positions,
                q_lens=tail_lens, backend=attn_backend, layer=li,
            )
        x, _ = _finish_layer(
            x, _attn_out(attn, lp, cfg), mp, cfg, mlp_kind, valid, banks, mi
        )
        return x, k_cache, v_cache

    layer_of = _layer_fns({'conv': conv_layer, 'attn': attn_layer}, cfg)
    for mixer, xi, mlp_kind, mi in cfg.layer_indices():
        shared = (
            x, *_layer_params(params, mixer, xi, mlp_kind, mi),
            params.get('sparse'), jnp.int32(mi),
        )
        if mixer == 'conv':
            x, convs[xi] = layer_of[mixer, mlp_kind](
                *shared, convs[xi], slots, fresh, tail_lens, valid
            )
        else:
            x, k_cache, v_cache = layer_of[mixer, mlp_kind](
                *shared, k_cache, v_cache, jnp.int32(xi), block_tables, cos,
                sin, positions, valid, context_lens, tail_lens,
            )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    last_hidden = common.last_token(hidden, tail_lens)
    state = {'conv': tuple(convs)}
    return logits(params, cfg, last_hidden)[:, 0], k_cache, v_cache, state


def _decode_core(
    params, cfg, rope, attn_backend, input_ids, positions, context_lens,
    caches, block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first four arguments are bound; ``caches`` is ``(k_cache, v_cache,
    state)``). The layers are walked unrolled: each conv
    layer's state is a buffer of its own, rewritten whole and in place
    (row ``i`` of the batch is slot ``i``), and a static slice of the
    stacked kernels folds into its matmul."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    k_cache, v_cache, state = caches
    x = common.embed(params, cfg.dtype, input_ids)  # [B, hidden]
    convs = list(state['conv'])
    pairs = jnp.zeros((2,), jnp.int32)

    def conv_layer(mlp_kind, x, lp, mp, banks, mi, conv0, live):
        normed = _norm(x, lp['ln']['scale'], cfg)
        with jax.named_scope('distllm.conv_decode'):
            mixed, conv = conv_step(normed, lp, conv0, live)
        x, layer_pairs = _finish_layer(
            x, mixed, mp, cfg, mlp_kind, live, banks, mi
        )
        return x, conv, layer_pairs

    def attn_layer(mlp_kind, x, lp, mp, banks, mi, k_cache, v_cache, li,
                   table, cos, sin, positions, context_lens, live):
        normed = _norm(x, lp['ln']['scale'], cfg)
        with jax.named_scope('distllm.attn_full'):
            q, k, v = _qkv(
                normed[:, None], lp, cfg, cos, sin, positions[:, None]
            )
            k_cache, v_cache = write_token_kv(
                k_cache, v_cache, k[:, 0], v[:, 0], table, positions, layer=li
            )
            attn = decode_attention(
                q[:, 0], k_cache, v_cache, table, context_lens, positions,
                backend=attn_backend, layer=li,
            )
        x, layer_pairs = _finish_layer(
            x, _attn_out(attn, lp, cfg), mp, cfg, mlp_kind, live, banks, mi
        )
        return x, k_cache, v_cache, layer_pairs

    layer_of = _layer_fns({'conv': conv_layer, 'attn': attn_layer}, cfg)
    for mixer, xi, mlp_kind, mi in cfg.layer_indices():
        shared = (
            x, *_layer_params(params, mixer, xi, mlp_kind, mi),
            params.get('sparse'), jnp.int32(mi),
        )
        if mixer == 'conv':
            x, convs[xi], layer_pairs = layer_of[mixer, mlp_kind](
                *shared, convs[xi], live
            )
        else:
            x, k_cache, v_cache, layer_pairs = layer_of[mixer, mlp_kind](
                *shared, k_cache, v_cache, jnp.int32(xi), block_tables,
                *rope, positions, context_lens, live,
            )
        pairs = pairs + layer_pairs
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    state = {'conv': tuple(convs)}
    return logits(params, cfg, hidden), (k_cache, v_cache, state), pairs


def decode_loop(  # distlint: traced
    params: dict,
    cfg: Lfm2MoeConfig,
    input_ids: jnp.ndarray,  # [B] last emitted token per slot
    positions: jnp.ndarray,  # [B]
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # covers + num_steps tokens
    context_lens: jnp.ndarray,
    steps_left: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    min_p: jnp.ndarray,
    top_k: jnp.ndarray,
    seeds: jnp.ndarray,
    num_steps: int,
    attn_backend: str = 'xla',
    max_table_positions: int | None = None,
    sampling_top_window: int = 0,
    *,
    state: dict,
):
    """``mistral.decode_loop``'s contract with the state pool beside the KV
    cache: row ``i`` of the batch is slot ``i`` of the pool (the batch is
    the scheduler's slots). A row out of budget writes its K/V to the trash
    block and leaves its state as it is. Returns ``(tokens [num_steps, B],
    k_cache, v_cache, last_ids, state, moe_pairs [2])``, the last being the
    window's (routed, held) pair counts over the rows and steps that ran."""
    rope = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    tokens, (k_cache, v_cache, state), ids, pairs = common.decode_window(
        functools.partial(_decode_core, params, cfg, rope, attn_backend),
        input_ids, positions, context_lens, (k_cache, v_cache, state),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=jnp.zeros((2,), jnp.int32),
    )
    return tokens, k_cache, v_cache, ids, state, pairs
