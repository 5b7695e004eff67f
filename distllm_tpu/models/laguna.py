"""Laguna (poolside ``laguna``: XS.2, S-2.1): full-attention layers between
runs of sliding-window layers, each kind with its own count of query heads
and its own rotation; a learned per-head sigmoid gate on the attention
output; a dense SwiGLU MLP in the leading layer(s) and, in the others, many
small routed experts scaled by ``moe_routed_scaling_factor`` plus one shared
expert.

Four stacked parameter trees: two for attention (``params['full']``,
``params['window']``: the kinds differ in the shapes of ``q``, ``gate`` and
``o``) and two for the MLP (``params['dense']``, ``params['sparse']``). The
dense forward walks ``cfg.layer_runs()`` and scans each run of equal layers;
the serving programs walk the layers unrolled, with static indices, each
layer a call of one jitted function a kind of layer (``common.once_a_kind``).

A sequence holds TWO kinds of K/V pages (``cfg.cache_spec()``): blocks of
the full layers' pool for its whole context, and blocks of the window
layers' pool for what a query can still see. The serving programs take a
pair of each cache operand, ``(full, window)``: ``k_cache``, ``v_cache`` and
``block_tables``; a group's ``k_cache`` is its stacked pool ``[L_kind,
num_blocks_kind, block_size, N_kv * Hd]``, handed whole to the writers and
the kernel with the layer whose pages are meant and written in place
(``ops.paged_attention``). Attention goes through the entry points ``models/
mistral.py`` calls (``common.sdpa``, ``ragged_paged_attention``,
``write_chunk_kv``, ``write_token_kv``) with a static window per kind; the
routed experts are ``models/moe.py``: a chip may hold a share of them
(``first_local_expert``, ``num_local_experts``) while the router ranks all
``num_experts``, and the shared expert is added here, once.

Equations, for layer ``l`` of kind ``t`` with ``H_l`` query heads, ``G`` KV
heads of ``d`` dims::

    h = rms(x);  q = h Wq [H_l, d];  k = h Wk [G, d];  v = h Wv [G, d]
    q, k = rope_t(q, k, pos)
    a = softmax(q k^T / sqrt(d) + mask_t) v      mask_window: i - w < j <= i
    g = sigmoid(h Wg) [H_l];  x = x + (g[:, None] * a) Wo
    h2 = rms(x)
    dense:   x = x + (silu(h2 Wg1) * (h2 Wu)) Wd
    sparse:  p = softmax(h2 Wr);  S = top_k(p);  w_e = s * p_e / sum_S p
             x = x + sum_{e in S} w_e E_e(h2) + E_shared(h2)

``rope_window`` rotates all ``d`` dims (plain RoPE); ``rope_full`` rotates the
first ``partial_rotary_factor * d`` dims with YaRN-scaled frequencies, cos
and sin times the attention factor, and passes the rest through.

What the published config leaves open is set by the family's sibling
(``Laguna-S-2.1``): the gate is per head (``gating: "per-head"`` there),
the kept gates are renormalised (``norm_topk_prob: true``), the router
scores by softmax. No QK-norm and no gate on the shared expert: the config
names neither. There is no ``params_from_hf``: the checkpoint's tensor
names cannot be read here, and a guessed converter would be worse than none.
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
# layer_types entry -> the attention tree (and cache group) of the layer
_ATTN_KINDS = {'full_attention': 'full', 'sliding_attention': 'window'}
_GROUPS = common.CACHE_GROUPS  # the order of the cache operands' entries
_BANKS = ('gate', 'up', 'down')


class LagunaConfig(BaseConfig):
    name: Literal['laguna'] = 'laguna'
    vocab_size: int = 100352
    hidden_size: int = 2048
    layer_types: tuple[
        Literal['full_attention', 'sliding_attention'], ...
    ] = ('full_attention',)
    mlp_layer_types: tuple[Literal['dense', 'sparse'], ...] = ('dense',)
    num_heads_per_layer: tuple[int, ...] = (48,)
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    # Per attention kind: rope_theta, rope_type ('default' | 'yarn'),
    # partial_rotary_factor and, for yarn, its parameters.
    rope_parameters: dict = {}
    intermediate_size: int = 8192  # width of a dense layer's MLP
    moe_intermediate_size: int = 512  # width of one routed expert
    shared_expert_intermediate_size: int = 512
    # The router ranks num_experts; this chip holds num_local_experts of
    # them, ids first_local_expert onward (all of them by default).
    num_experts: int = 256
    num_local_experts: int = 256
    first_local_expert: int = 0
    experts_per_token: int = 8
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    dtype: str = 'bfloat16'

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_size(self) -> int:
        return self.head_dim

    def attn_kind(self, layer: int) -> str:
        return _ATTN_KINDS[self.layer_types[layer]]

    def num_heads(self, kind: str) -> int:
        """Query heads of the layers of one attention kind (one count a
        kind: the kind's layers are one stacked tree)."""
        return next(
            h for h, t in zip(self.num_heads_per_layer, self.layer_types)
            if _ATTN_KINDS[t] == kind
        )

    def count(self, kind: str) -> int:
        """Layers of an attention kind or of an MLP kind."""
        if kind in _GROUPS:
            return sum(_ATTN_KINDS[t] == kind for t in self.layer_types)
        return self.mlp_layer_types.count(kind)

    def window(self, kind: str) -> int | None:
        return self.sliding_window if kind == 'window' else None

    def layer_runs(self) -> list[tuple[str, str, int, int, int]]:
        """``(attention kind, MLP kind, first index in the attention tree,
        first index in the MLP tree, count)`` of every run of equal
        consecutive layers."""
        return common.layer_runs([
            (self.attn_kind(layer), mlp)
            for layer, mlp in enumerate(self.mlp_layer_types)
        ])

    def layer_indices(self) -> list[tuple[str, str, int, int]]:
        """``(attention kind, MLP kind, index in the attention tree, index
        in the MLP tree)`` of every layer."""
        return common.layer_indices(self.layer_runs())

    def cache_spec(self) -> common.CacheSpec:
        """Two paged groups: the full layers' blocks hold whole contexts,
        the window layers' only what a query still sees."""
        return common.CacheSpec(
            paged=tuple(
                common.PagedGroup(kind, self.count(kind), self.window(kind))
                for kind in _GROUPS
            ),
            programs=__name__,
            program_prefix='laguna_',
            dense_prefill=False,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'LagunaConfig':
        """The published keys as they are, plus two that state a chip's
        share as ``granitemoehybrid``'s configuration does
        (``num_routed_experts``: the router's width where ``num_experts``
        counts the experts held; ``first_local_expert``). Values this
        module does not implement are refused."""
        if hf.get('gating', True) not in (True, 'per-head'):
            raise ValueError(
                'laguna: only a per-head attention gate (gating true or '
                f'"per-head") is implemented, got {hf["gating"]!r}'
            )
        if any(t != 'per_head' for t in hf.get('gating_types', ())):
            raise ValueError('laguna: only per_head gating_types implemented')
        if hf.get('moe_apply_router_weight_on_input', False):
            raise ValueError(
                'laguna: moe_apply_router_weight_on_input is not implemented'
            )
        if hf.get('attention_bias', False) or hf.get('mlp_bias', False):
            raise ValueError('laguna: projection biases are not implemented')
        if not hf.get('norm_topk_prob', True):
            raise ValueError('laguna: norm_topk_prob false is not implemented')
        if hf.get('moe_router_logit_softcapping', 0):
            raise ValueError('laguna: router logit softcapping not implemented')
        if hf.get('tie_word_embeddings', False):
            raise ValueError('laguna: a tied output head is not implemented')
        layers = hf['num_hidden_layers']
        layer_types = tuple(hf['layer_types'])
        heads = tuple(
            hf.get('num_attention_heads_per_layer')
            or [hf['num_attention_heads']] * layers
        )
        mlp_types = hf.get('mlp_layer_types')
        if mlp_types is None:  # the sibling's spelling
            dense = set(hf.get('mlp_only_layers', ()))
            mlp_types = ['dense' if i in dense else 'sparse' for i in range(layers)]
        if not len(layer_types) == len(heads) == len(mlp_types) == layers:
            raise ValueError(
                'laguna: layer_types, mlp_layer_types and '
                'num_attention_heads_per_layer must each have '
                f'num_hidden_layers={layers} entries'
            )
        rope = {}
        for hf_kind, kind in _ATTN_KINDS.items():
            spec = dict(hf['rope_parameters'][hf_kind])
            if spec.get('rope_type', 'default') not in ('default', 'yarn'):
                raise ValueError(
                    f'laguna: rope_type {spec["rope_type"]!r} is not '
                    'implemented (default, yarn)'
                )
            rope[kind] = spec
            of_kind = {h for h, t in zip(heads, layer_types) if t == hf_kind}
            if len(of_kind) > 1:
                raise ValueError(
                    f'laguna: {hf_kind} layers with different head counts '
                    f'{sorted(of_kind)} are not implemented'
                )
        if len(set(layer_types)) < 2:
            raise ValueError(
                'laguna: layers of one attention kind only are not '
                'implemented (the programs take a cache group a kind)'
            )
        held = hf['num_experts']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            layer_types=layer_types,
            mlp_layer_types=tuple(mlp_types),
            num_heads_per_layer=heads,
            num_kv_heads=hf['num_key_value_heads'],
            head_dim=hf['head_dim'],
            sliding_window=hf['sliding_window'],
            rope_parameters=rope,
            intermediate_size=hf['intermediate_size'],
            moe_intermediate_size=hf['moe_intermediate_size'],
            shared_expert_intermediate_size=hf['shared_expert_intermediate_size'],
            num_experts=hf.get('num_routed_experts', held),
            num_local_experts=held,
            first_local_expert=hf.get('first_local_expert', 0),
            experts_per_token=hf['num_experts_per_tok'],
            routed_scaling_factor=hf.get('moe_routed_scaling_factor', 1.0),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-6),
            max_position_embeddings=hf.get('max_position_embeddings', 262144),
        )


# ------------------------------------------------------------- parameters
def _tree_shapes(cfg: LagunaConfig, kind: str) -> dict:
    """``name -> shape`` of one layer's parameters in the tree ``kind``
    (kernels ``[in, out]``)."""
    h, d = cfg.hidden_size, cfg.head_dim
    if kind in _GROUPS:
        heads, kv_out = cfg.num_heads(kind), cfg.num_kv_heads * d
        return {
            'ln': (h,), 'q': (h, heads * d), 'k': (h, kv_out), 'v': (h, kv_out),
            'attn_gate': (h, heads), 'o': (heads * d, h),
        }
    if kind == 'dense':
        i = cfg.intermediate_size
        return {'mlp_ln': (h,), 'gate': (h, i), 'up': (h, i), 'down': (i, h)}
    i, s, e = (
        cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
        cfg.num_local_experts,
    )
    return {
        'mlp_ln': (h,), 'router': (h, cfg.num_experts),
        'gate': (e, h, i), 'up': (e, h, i), 'down': (e, i, h),
        'shared_gate': (h, s), 'shared_up': (h, s), 'shared_down': (s, h),
    }


_TREES = (*_GROUPS, 'dense', 'sparse')
_SCALES = ('ln', 'mlp_ln')  # {'scale': ...} leaves; the rest {'kernel': ...}


def _wrap(name: str, leaf):
    return {'scale' if name in _SCALES else 'kernel': leaf}


def _top_shapes(cfg: LagunaConfig) -> dict:
    return {
        'embed': (cfg.vocab_size, cfg.hidden_size),
        'lm_head': (cfg.hidden_size, cfg.vocab_size),
    }


def _trees(cfg: LagunaConfig) -> dict:
    return common.tree_table(
        _TREES, cfg.count, lambda kind: _tree_shapes(cfg, kind)
    )


def init_on_device(rng: jax.Array, cfg: LagunaConfig) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels, unit norm scales, one RNG call per parameter kind."""
    return common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size, _top_shapes(cfg), _trees(cfg), _wrap,
        _SCALES,
    )


def param_specs(cfg: LagunaConfig, params: dict | None = None) -> dict:
    """Expert banks over ``expert``, everything else replicated."""
    return common.tree_specs(
        _top_shapes(cfg), _trees(cfg), _wrap, [('sparse', n) for n in _BANKS]
    )


def params_from_hf(state: dict, cfg: LagunaConfig) -> dict:
    raise NotImplementedError(
        'laguna: no converter from a published checkpoint yet (the tensor '
        'names of poolside/Laguna-* could not be read where this module was '
        'written); serve seeded weights (init_on_device)'
    )


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.rms_norm_eps)


def _rope_tables(cfg: LagunaConfig, max_len: int) -> dict:
    """``kind -> (cos, sin, rotated dims)``: tables ``[max_len, rotated /
    2]``, YaRN's attention factor already on them."""
    tables = {}
    for kind in _GROUPS:
        spec = cfg.rope_parameters[kind]
        rotated = int(cfg.head_dim * spec.get('partial_rotary_factor', 1.0))
        cos, sin = common.rope_frequencies(
            rotated, max_len, float(spec['rope_theta']), spec
        )
        tables[kind] = (jnp.asarray(cos), jnp.asarray(sin), rotated)
    return tables


def _rope(x, table, positions):
    """Rotate the first ``rotated`` dims of ``x [B, S, N, d]`` (HF's
    rotate_half pairing inside them); the rest pass through."""
    cos, sin, rotated = table
    if rotated == x.shape[-1]:
        return common.apply_rope(x, cos, sin, positions)
    return jnp.concatenate([
        common.apply_rope(x[..., :rotated], cos, sin, positions),
        x[..., rotated:],
    ], axis=-1)


def _qkv(normed, lp, cfg, kind):
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_dim)  # noqa: E731
    return (
        heads(common.dense(normed, lp['q']['kernel']), cfg.num_heads(kind)),
        heads(common.dense(normed, lp['k']['kernel']), cfg.num_kv_heads),
        heads(common.dense(normed, lp['v']['kernel']), cfg.num_kv_heads),
    )


def _attn_out(attn, normed, lp, cfg, kind):
    """The per-head sigmoid gate of the layer's input on the attention
    output, then the output projection."""
    gate = jax.nn.sigmoid(
        common.dense(normed, lp['attn_gate']['kernel']).astype(F32)
    )
    gated = (attn.astype(F32) * gate[..., None]).astype(attn.dtype)
    return common.dense(
        gated.reshape(*gated.shape[:-2], cfg.num_heads(kind) * cfg.head_dim),
        lp['o']['kernel'],
    )


def _mlp(x, mp, cfg, mlp_kind, counted, banks, mi):
    """The MLP block of one layer for ``x [T, H]`` (already normed);
    returns it and the layer's (routed, held) pair counts. ``banks`` is
    the sparse tree: the expert banks stay stacked, ``mi`` picks the layer
    inside the expert matmuls (``models/moe.py``)."""
    if mlp_kind == 'dense':
        return common.dense_mlp(x, mp), jnp.zeros((2,), jnp.int32)
    routed, pairs = routed_experts(
        x, mp['router']['kernel'], *(banks[n]['kernel'] for n in _BANKS),
        cfg.experts_per_token, first_expert=cfg.first_local_expert,
        counted=counted, layer=mi, routed_scale=cfg.routed_scaling_factor,
    )
    # The shared expert: every chip of the expert axis computes it alike,
    # so it is counted once, here, whatever share of the bank is held.
    with jax.named_scope('distllm.moe'):
        shared = common.swiglu(
            x, mp['shared_gate']['kernel'], mp['shared_up']['kernel'],
            mp['shared_down']['kernel'],
        )
    return routed + shared, pairs


def _finish_layer(x, mixed, mp, cfg, mlp_kind, counted, banks, mi):
    """Residual of the attention output, then the MLP block."""
    return common.finish_layer(
        x, mixed, mp, cfg.rms_norm_eps,
        lambda rows, of_rows: _mlp(rows, mp, cfg, mlp_kind, of_rows, banks, mi),
        counted,
    )


def logits(params: dict, cfg: LagunaConfig, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; the untied head over the held
    slice of the vocabulary."""
    return common.dense(hidden, params['lm_head']).astype(F32)


def _mlp_layer_at(params, mlp_kind, mi):
    return common.layer_at(
        params[mlp_kind], mi, skip=_BANKS if mlp_kind == 'sparse' else ()
    )


# ----------------------------------------------------------------- forwards
def apply(  # distlint: traced
    params: dict,
    cfg: LagunaConfig,
    input_ids: jnp.ndarray,  # [B, S], right-padded
    attention_mask: jnp.ndarray,  # [B, S]
) -> jnp.ndarray:
    """Dense causal forward, no cache: ``[B, S]`` -> final-normed hidden
    states ``[B, S, H]``."""
    b, s = input_ids.shape
    valid = attention_mask.astype(bool)
    causal = common.causal_mask(s, s)
    near = jnp.arange(s)[None, :] > jnp.arange(s)[:, None] - cfg.sliding_window
    masks = {
        'full': causal[None, None] & valid[:, None, None, :],
        'window': (causal & near)[None, None] & valid[:, None, None, :],
    }
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    rope = _rope_tables(cfg, s)
    x = common.embed(params, cfg.dtype, input_ids)
    for attn_kind, mlp_kind, first_a, first_m, count in cfg.layer_runs():

        def layer(x, xs, attn_kind=attn_kind, mlp_kind=mlp_kind):
            ai, mi = xs
            lp = common.layer_at(params[attn_kind], ai)
            mp = _mlp_layer_at(params, mlp_kind, mi)
            normed = _norm(x, lp['ln']['scale'], cfg)
            q, k, v = _qkv(normed, lp, cfg, attn_kind)
            q = _rope(q, rope[attn_kind], positions)
            k = _rope(k, rope[attn_kind], positions)
            with jax.named_scope(f'distllm.attn_{attn_kind}'):
                attn = common.sdpa(q, k, v, mask=masks[attn_kind])
            x, _ = _finish_layer(
                x, _attn_out(attn, normed, lp, cfg, attn_kind), mp, cfg,
                mlp_kind, valid, params.get('sparse'), mi,
            )
            return x, None

        x, _ = jax.lax.scan(
            layer, x, common.run_indices(first_a, first_m, count)
        )
    return _norm(x, params['final_ln']['scale'], cfg)


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: LagunaConfig,
    input_ids: jnp.ndarray,  # [B, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache,  # (full, window): [L_kind, num_blocks_kind, block_size, N_kv * Hd]
    v_cache,
    block_tables,  # (full, window): [B, max_blocks] each
    context_lens: jnp.ndarray,  # [B] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    max_table_positions: int | None = None,
    attn_backend: str = 'xla',
):
    """One span of every row through the paged path: a whole prompt, or
    one chunk of a long one. Each layer writes the span's K/V into its
    group's pool first, then the span's queries attend over the pages: in a
    window layer those of the last ``sliding_window`` positions, which the
    row's window table still names (entries behind them are the trash
    block, never fetched). Returns ``(last_logits [B, V] float32, k_cache,
    v_cache)``, the caches as the pairs they came in as. The layers are
    walked unrolled, each writing its own pages of its group's pool."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention,
        write_chunk_kv,
    )

    s = input_ids.shape[1]
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    rope = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    x = common.embed(params, cfg.dtype, input_ids)

    def layer(attn_kind, mlp_kind, x, lp, mp, banks, mi, k_cache, v_cache, li,
              table, cos, sin, positions, valid, context_lens, tail_lens):
        normed = _norm(x, lp['ln']['scale'], cfg)
        q, k, v = _qkv(normed, lp, cfg, attn_kind)
        q = _rope(q, (cos, sin, 2 * cos.shape[-1]), positions)
        k = _rope(k, (cos, sin, 2 * cos.shape[-1]), positions)
        with jax.named_scope(f'distllm.attn_{attn_kind}'):
            # the stacked pools whole, with the layer whose pages are meant
            k_cache, v_cache = write_chunk_kv(
                k_cache, v_cache, k, v, table, positions, valid, layer=li
            )
            attn = ragged_paged_attention(
                q, k_cache, v_cache, table, context_lens, positions,
                q_lens=tail_lens, sliding_window=cfg.window(attn_kind),
                backend=attn_backend, layer=li,
            )
        x, _ = _finish_layer(
            x, _attn_out(attn, normed, lp, cfg, attn_kind), mp, cfg, mlp_kind,
            valid, banks, mi,
        )
        return x, k_cache, v_cache

    x, k_cache, v_cache = common.walk_cache_groups(
        layer, cfg.layer_indices(), 'laguna_layer', x, k_cache, v_cache,
        block_tables, functools.partial(_layer_weights, params),
        lambda kind: (
            *rope[kind[0]][:2], positions, valid, context_lens, tail_lens,
        ),
    )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    last_hidden = common.last_token(hidden, tail_lens)
    return logits(params, cfg, last_hidden)[:, 0], k_cache, v_cache


def _layer_weights(params, kind, ai, mi):
    """A layer's operands of the serving walk: its attention and MLP
    parameters, the sparse tree (its banks stay stacked) and the layer's
    index in it."""
    attn_kind, mlp_kind = kind
    return (
        common.layer_at(params[attn_kind], ai),
        _mlp_layer_at(params, mlp_kind, mi), params.get('sparse'),
        jnp.int32(mi),
    )


def _decode_core(
    params, cfg, rope, attn_backend, input_ids, positions, context_lens,
    caches, block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first four arguments are bound; ``caches`` is ``(k_cache, v_cache)``).
    The layers are walked unrolled, each with static indices into the
    weights: a static slice of the stacked kernels folds into its matmul,
    and a layer's pages of its group's pool are written in place."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    x = common.embed(params, cfg.dtype, input_ids)  # [B, H]

    def layer(attn_kind, mlp_kind, x, lp, mp, banks, mi, k_cache, v_cache, li,
              table, cos, sin, positions, context_lens, live):
        normed = _norm(x, lp['ln']['scale'], cfg)
        q, k, v = _qkv(normed, lp, cfg, attn_kind)
        table_of_kind = (cos, sin, 2 * cos.shape[-1])
        q = _rope(q[:, None], table_of_kind, positions[:, None])[:, 0]
        k = _rope(k[:, None], table_of_kind, positions[:, None])[:, 0]
        with jax.named_scope(f'distllm.attn_{attn_kind}'):
            k_cache, v_cache = write_token_kv(
                k_cache, v_cache, k, v, table, positions, layer=li
            )
            attn = decode_attention(
                q, k_cache, v_cache, table, context_lens, positions,
                backend=attn_backend, sliding_window=cfg.window(attn_kind),
                layer=li,
            )
        x, layer_pairs = _finish_layer(
            x, _attn_out(attn, normed, lp, cfg, attn_kind), mp, cfg, mlp_kind,
            live, banks, mi,
        )
        return x, k_cache, v_cache, layer_pairs

    x, k_cache, v_cache, pairs = common.walk_cache_groups(
        layer, cfg.layer_indices(), 'laguna_layer', x, *caches, block_tables,
        functools.partial(_layer_weights, params),
        lambda kind: (*rope[kind[0]][:2], positions, context_lens, live),
        counts=jnp.zeros((2,), jnp.int32),
    )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    return logits(params, cfg, hidden), (k_cache, v_cache), pairs


def decode_loop(  # distlint: traced
    params: dict,
    cfg: LagunaConfig,
    input_ids: jnp.ndarray,  # [B] last emitted token per slot
    positions: jnp.ndarray,  # [B]
    k_cache,  # (full, window)
    v_cache,
    block_tables,  # (full, window): each covers + num_steps tokens
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
):
    """``mistral.decode_loop``'s contract over the two cache groups. A row
    out of budget writes its K/V to the trash block of both pools. Returns
    ``(tokens [num_steps, B], k_cache, v_cache, last_ids, moe_pairs [2])``,
    the last being the window's (routed, held) pair counts over the rows
    and steps that ran."""
    rope = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    tokens, (k_cache, v_cache), ids, pairs = common.decode_window(
        functools.partial(_decode_core, params, cfg, rope, attn_backend),
        input_ids, positions, context_lens, (tuple(k_cache), tuple(v_cache)),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=jnp.zeros((2,), jnp.int32),
    )
    return tokens, k_cache, v_cache, ids, pairs
