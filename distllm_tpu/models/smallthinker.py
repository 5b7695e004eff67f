"""SmallThinker (PowerInfer ``smallthinker``: 21B-A3B, 4B-A0.6B): every layer
a GQA attention block and a layer of routed ReLU-gated experts whose ROUTER
reads the layer's input-normed stream, the tensor attention reads, while the
experts read the post-attention-normed stream; per-layer lists say which
layers attend over a sliding window and which rotate their queries and keys
(published: one NoPE full-attention layer to three RoPE layers of window
4096). No shared expert, no dense layer, no bias, no q/k norm.

Three stacked parameter trees: two for attention (``params['full']``,
``params['window']``: a layer's cache group is its tree) and one for the
expert layer of every layer (``params['sparse']``). The dense forward scans
each run of equal layers (``common.layer_runs``); the serving programs are
``common.walk_cache_groups``, the walk over two cache groups that
``models/laguna.py`` takes too: a pair of each cache operand, ``(full,
window)``, a group's ``k_cache`` its stacked pool, addressed by layer.

The expert layer is ``models/moe.py``: the ranking (``moe.rank_experts``:
router logits, top-k, gates, and for the grouped form the pairs' sort) is
made where the router's input is, ahead of attention, under the scope
``distllm.moe_route``; the matmuls take it behind attention
(``routed_experts(..., ranking=, activation='relu')``, scope
``distllm.moe``). A chip may hold a share of the experts
(``first_local_expert``, ``num_local_experts``) while the router ranks all
``num_experts``.

Equations, for layer ``l``, ``d = head_dim``::

    u = rms(x; w1)
    r = u Wr [E];  S = top_k(r);  g_e = softmax over S of r_e
    q = u Wq [N, d];  k = u Wk [G, d];  v = u Wv [G, d]
    q, k = rope(q, k, pos)            if rope_layout[l] else as they are
    a = softmax(q k^T / sqrt(d) + mask) v
        mask causal, and i - w < j    if sliding_window_layout[l]
    h = x + a Wo;  m = rms(h; w2)
    x = h + sum_{e in S} g_e (relu(m G_e) * (m U_e)) D_e

What the published config leaves open, as ``benchmarks/configs/
smallthinker-21b-a3b.json`` lists under ``assumed``: the router's input is
``u`` ("router placed before attention"), the gate non-linearity ReLU
("sparse ReGLU"), a zero in ``rope_layout`` no rotation at all, one level of
experts.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import common
from distllm_tpu.models.moe import rank_experts, routed_experts
from distllm_tpu.utils import BaseConfig

F32 = jnp.float32
_GROUPS = common.CACHE_GROUPS  # the order of the cache operands' entries
_BANKS = ('gate', 'up', 'down')
_ROTATIONS = ('nope', 'rope')  # rope_layout's 0 and 1


class SmallThinkerConfig(BaseConfig):
    name: Literal['smallthinker'] = 'smallthinker'
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    # Per layer: 1 = a window of ``sliding_window``, 0 = the whole context;
    # 1 = RoPE over the whole head, 0 = no rotation.
    sliding_window_layout: tuple[int, ...] = (0, 1)
    rope_layout: tuple[int, ...] = (0, 1)
    sliding_window: int = 4096
    rope_theta: float = 1.5e6
    moe_intermediate_size: int = 768  # width of one expert
    # The router ranks num_experts; this chip holds num_local_experts of
    # them, ids first_local_expert onward (all of them by default).
    num_experts: int = 64
    num_local_experts: int = 64
    first_local_expert: int = 0
    experts_per_token: int = 6
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 16384
    dtype: str = 'bfloat16'

    @property
    def num_layers(self) -> int:
        return len(self.sliding_window_layout)

    @property
    def head_size(self) -> int:
        return self.head_dim

    def count(self, tree: str) -> int:
        """Layers of a stacked tree."""
        if tree == 'sparse':
            return self.num_layers
        return sum(_GROUPS[w] == tree for w in self.sliding_window_layout)

    def window(self, group: str) -> int | None:
        return self.sliding_window if group == 'window' else None

    def layer_runs(self) -> list[tuple[str, str, int, int, int]]:
        """``(cache group, rotation, first index in the group's tree, first
        index in the sparse tree, count)`` of every run of equal consecutive
        layers."""
        kinds = [
            (_GROUPS[w], _ROTATIONS[r])
            for w, r in zip(self.sliding_window_layout, self.rope_layout)
        ]
        return common.layer_runs(
            kinds, [(group, 'sparse') for group, _ in kinds]
        )

    def layer_indices(self) -> list[tuple[str, str, int, int]]:
        """``(cache group, rotation, index in the group's tree, layer)`` of
        every layer."""
        return common.layer_indices(self.layer_runs())

    def cache_spec(self) -> common.CacheSpec:
        """Two paged groups: the full layers' blocks hold whole contexts,
        the window layers' only what a query still sees."""
        return common.CacheSpec(
            paged=tuple(
                common.PagedGroup(g, self.count(g), self.window(g))
                for g in _GROUPS
            ),
            programs=__name__,
            program_prefix='smallthinker_',
            dense_prefill=False,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'SmallThinkerConfig':
        """The published keys as they are, plus two that state a chip's
        share as ``laguna``'s configuration does (``num_routed_experts``:
        the router's width where ``moe_num_primary_experts`` counts the
        experts held; ``first_local_expert``). Values this module does not
        implement are refused."""
        if not hf.get('moe_primary_router_apply_softmax', True):
            raise ValueError(
                'smallthinker: moe_primary_router_apply_softmax false (sigmoid '
                'gates) is not implemented'
            )
        if not hf.get('norm_topk_prob', True):
            raise ValueError(
                'smallthinker: norm_topk_prob false is not implemented'
            )
        if hf.get('rope_scaling'):
            raise ValueError('smallthinker: a rope_scaling is not implemented')
        if hf.get('tie_word_embeddings', False):
            raise ValueError(
                'smallthinker: a tied output head is not implemented'
            )
        layers = hf['num_hidden_layers']
        windows = tuple(int(w) for w in hf['sliding_window_layout'])
        ropes = tuple(int(r) for r in hf['rope_layout'])
        if not len(windows) == len(ropes) == layers:
            raise ValueError(
                'smallthinker: sliding_window_layout and rope_layout must '
                f'each have num_hidden_layers={layers} entries'
            )
        if set(windows) - {0, 1} or set(ropes) - {0, 1}:
            raise ValueError(
                'smallthinker: layout entries other than 0 and 1 (more than '
                'one window size) are not implemented'
            )
        if len(set(windows)) < 2:
            raise ValueError(
                'smallthinker: a model with no window layer or no full layer '
                'is not implemented (the programs take a cache group a kind)'
            )
        held = hf['moe_num_primary_experts']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_heads=hf['num_attention_heads'],
            num_kv_heads=hf['num_key_value_heads'],
            head_dim=hf['head_dim'],
            sliding_window_layout=windows,
            rope_layout=ropes,
            sliding_window=hf['sliding_window_size'],
            rope_theta=float(hf['rope_theta']),
            moe_intermediate_size=hf['moe_ffn_hidden_size'],
            num_experts=hf.get('num_routed_experts', held),
            num_local_experts=held,
            first_local_expert=hf.get('first_local_expert', 0),
            experts_per_token=hf['moe_num_active_primary_experts'],
            rms_norm_eps=hf.get('rms_norm_eps', 1e-6),
            max_position_embeddings=hf.get('max_position_embeddings', 16384),
        )


# ------------------------------------------------------------- parameters
def _tree_shapes(cfg: SmallThinkerConfig, tree: str) -> dict:
    """``name -> shape`` of one layer's parameters in a stacked tree
    (kernels ``[in, out]``)."""
    h, d = cfg.hidden_size, cfg.head_dim
    if tree in _GROUPS:
        q_out, kv_out = cfg.num_heads * d, cfg.num_kv_heads * d
        return {
            'ln': (h,), 'q': (h, q_out), 'k': (h, kv_out), 'v': (h, kv_out),
            'o': (q_out, h),
        }
    i, e = cfg.moe_intermediate_size, cfg.num_local_experts
    return {
        'mlp_ln': (h,), 'router': (h, cfg.num_experts),
        'gate': (e, h, i), 'up': (e, h, i), 'down': (e, i, h),
    }


_TREES = (*_GROUPS, 'sparse')
_SCALES = ('ln', 'mlp_ln')  # {'scale': ...} leaves; the rest {'kernel': ...}


def _wrap(name: str, leaf):
    return {'scale' if name in _SCALES else 'kernel': leaf}


def _top_shapes(cfg: SmallThinkerConfig) -> dict:
    return {
        'embed': (cfg.vocab_size, cfg.hidden_size),
        'head': (cfg.hidden_size, cfg.vocab_size),
    }


def _trees(cfg: SmallThinkerConfig) -> dict:
    return common.tree_table(
        _TREES, cfg.count, lambda tree: _tree_shapes(cfg, tree)
    )


def init_on_device(rng: jax.Array, cfg: SmallThinkerConfig) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels, unit norm scales, one RNG call per parameter kind."""
    return common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size, _top_shapes(cfg), _trees(cfg), _wrap,
        _SCALES,
    )


def param_specs(cfg: SmallThinkerConfig, params: dict | None = None) -> dict:
    """Expert banks over ``expert``, everything else replicated."""
    return common.tree_specs(
        _top_shapes(cfg), _trees(cfg), _wrap, [('sparse', n) for n in _BANKS]
    )


# Held a layer an array in the tree the programs are served from: at the
# cell's depth the compiler merges the 16 layers' static slices of these
# stacks into fusions that write every layer's kernel out again each step
# (``tests/test_aot_tpu.py::test_decode_window_slices_no_weight``: 12 x
# bf16[2560, 3584], 4 more, and 12 x bf16[2560, 512] at the parent's form).
_PER_LAYER = ('q', 'k', 'v')


def serving_params(params: dict, own: bool = False) -> dict:
    """The tree the serving programs read, of the public tree
    (``init_on_device``'s, ``param_specs``'): the same arrays, with each
    attention stack of ``_PER_LAYER`` a tuple of its layers
    (``common.unstack``), as ``deepseek_v3.serving_params`` says why. The
    engine calls this once, before it compiles; ``own`` deletes each stack
    as its layers stand. ``params`` itself is not changed; ``apply`` scans
    its layers and takes the public tree."""
    out = dict(params)
    for group in _GROUPS:
        tree = dict(params[group])
        for name in _PER_LAYER:
            tree[name] = {'kernel': common.unstack(tree[name]['kernel'], own)}
        out[group] = tree
    return out


def params_from_hf(state: dict, cfg: SmallThinkerConfig) -> dict:
    """The published checkpoint's tensors (``model.layers.N.self_attn.
    {q,k,v,o}_proj``, ``input_layernorm``, ``post_attention_layernorm``,
    ``block_sparse_moe.primary_router`` and ``block_sparse_moe.experts.E.
    {gate,up,down}``; ``nn.Linear`` weights ``[out, in]``) as this module's
    tree, the experts held here alone. The names are those of the published
    modelling code as they were known where this was written, with no
    checkpoint at hand to read: a name that is not there is refused by
    name, never guessed around."""

    def take(name):
        if name not in state:
            raise KeyError(
                f'smallthinker: the checkpoint has no tensor {name!r}; '
                'params_from_hf knows the published names alone'
            )
        return np.asarray(state[name])

    def kernel(name):
        return np.ascontiguousarray(take(f'{name}.weight').T)

    trees: dict = {tree: [] for tree in _TREES}
    first, held = cfg.first_local_expert, cfg.num_local_experts
    for layer, window in enumerate(cfg.sliding_window_layout):
        at = f'model.layers.{layer}'
        trees[_GROUPS[window]].append({
            'ln': {'scale': take(f'{at}.input_layernorm.weight')},
            **{
                n: {'kernel': kernel(f'{at}.self_attn.{n}_proj')}
                for n in ('q', 'k', 'v', 'o')
            },
        })
        moe = f'{at}.block_sparse_moe'
        trees['sparse'].append({
            'mlp_ln': {'scale': take(f'{at}.post_attention_layernorm.weight')},
            'router': {'kernel': kernel(f'{moe}.primary_router')},
            **{
                n: {'kernel': np.stack([
                    kernel(f'{moe}.experts.{e}.{n}')
                    for e in range(first, first + held)
                ])}
                for n in _BANKS
            },
        })
    return {
        'embed': take('model.embed_tokens.weight'),
        'head': kernel('lm_head'),
        'final_ln': {'scale': take('model.norm.weight')},
        **{tree: common.stack_layers(layers) for tree, layers in trees.items()},
    }


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.rms_norm_eps)


def _rope_table(cfg: SmallThinkerConfig, max_len: int):
    cos, sin = common.rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def _rank(u, mp, banks, cfg, mi):
    """The layer's ranking from ``u [..., H]``, the stream ATTENTION reads:
    what ``_experts`` hands ``routed_experts`` behind attention."""
    with jax.named_scope('distllm.moe_route'):
        return rank_experts(
            u.reshape(-1, u.shape[-1]), mp['router']['kernel'],
            cfg.experts_per_token, banks['gate']['kernel'].shape,
            first_expert=cfg.first_local_expert, layer=mi,
        )


def _qkv(u, lp, cfg, rotation, cos, sin, positions):
    """``(q [B, S, N, d], k, v [B, S, G, d])`` of ``u [B, S, H]``, ``q`` and
    ``k`` rotated where the layer's ``rope_layout`` says so."""
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_dim)  # noqa: E731
    q = heads(common.dense(u, lp['q']['kernel']), cfg.num_heads)
    k = heads(common.dense(u, lp['k']['kernel']), cfg.num_kv_heads)
    v = heads(common.dense(u, lp['v']['kernel']), cfg.num_kv_heads)
    if rotation == 'rope':
        q = common.apply_rope(q, cos, sin, positions)
        k = common.apply_rope(k, cos, sin, positions)
    return q, k, v


def _finish_layer(x, attn, lp, mp, banks, cfg, mi, ranking, counted):
    """The output projection and its residual, then the experts over the
    post-attention norm's rows with the ranking made ahead."""

    def experts(rows, of_rows):
        return routed_experts(
            rows, mp['router']['kernel'], *(banks[n]['kernel'] for n in _BANKS),
            cfg.experts_per_token, first_expert=cfg.first_local_expert,
            counted=of_rows, layer=mi, activation='relu', ranking=ranking,
        )

    mixed = common.dense(
        attn.reshape(*attn.shape[:-2], cfg.num_heads * cfg.head_dim),
        lp['o']['kernel'],
    )
    return common.finish_layer(x, mixed, mp, cfg.rms_norm_eps, experts, counted)


def logits(params: dict, cfg: SmallThinkerConfig, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; the untied head."""
    with jax.named_scope('distllm.head'):
        return common.dense(hidden, params['head']).astype(F32)


def _layer_weights(params, kind, ai, mi):
    """A layer's operands of the serving walk: its attention parameters, its
    expert layer's without the banks, the sparse tree (the banks stay
    stacked) and the layer's index in it."""
    return (
        common.layer_at(params[kind[0]], ai),
        common.layer_at(params['sparse'], mi, skip=_BANKS), params['sparse'],
        jnp.int32(mi),
    )


# ----------------------------------------------------------------- forwards
def apply(  # distlint: traced
    params: dict,
    cfg: SmallThinkerConfig,
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
    cos, sin = _rope_table(cfg, s)
    x = common.embed(params, cfg.dtype, input_ids)
    for group, rotation, first_a, first_m, count in cfg.layer_runs():

        def layer(x, xs, group=group, rotation=rotation):
            ai, mi = xs
            lp = common.layer_at(params[group], ai)
            mp = common.layer_at(params['sparse'], mi, skip=_BANKS)
            u = _norm(x, lp['ln']['scale'], cfg)
            ranking = _rank(u, mp, params['sparse'], cfg, mi)
            q, k, v = _qkv(u, lp, cfg, rotation, cos, sin, positions)
            with jax.named_scope(f'distllm.attn_{group}'):
                attn = common.sdpa(q, k, v, mask=masks[group])
            x, _ = _finish_layer(
                x, attn, lp, mp, params['sparse'], cfg, mi, ranking, valid
            )
            return x, None

        x, _ = jax.lax.scan(
            layer, x, common.run_indices(first_a, first_m, count)
        )
    return _norm(x, params['final_ln']['scale'], cfg)


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: SmallThinkerConfig,
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
    """One span of every row through the paged path (``laguna.
    prefill_paged``'s contract): each layer writes the span's K/V into its
    group's pool, then the span's queries attend over the pages. Returns
    ``(last_logits [B, V] float32, k_cache, v_cache)``."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention,
        write_chunk_kv,
    )

    s = input_ids.shape[1]
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    rope = _rope_table(cfg, max_table_positions or cfg.max_position_embeddings)
    x = common.embed(params, cfg.dtype, input_ids)

    def layer(group, rotation, x, lp, mp, banks, mi, k_cache, v_cache, li,
              table, cos, sin, positions, valid, context_lens, tail_lens):
        u = _norm(x, lp['ln']['scale'], cfg)
        ranking = _rank(u, mp, banks, cfg, mi)
        q, k, v = _qkv(u, lp, cfg, rotation, cos, sin, positions)
        with jax.named_scope(f'distllm.attn_{group}'):
            k_cache, v_cache = write_chunk_kv(
                k_cache, v_cache, k, v, table, positions, valid, layer=li
            )
            attn = ragged_paged_attention(
                q, k_cache, v_cache, table, context_lens, positions,
                q_lens=tail_lens, sliding_window=cfg.window(group),
                backend=attn_backend, layer=li,
            )
        x, _ = _finish_layer(x, attn, lp, mp, banks, cfg, mi, ranking, valid)
        return x, k_cache, v_cache

    x, k_cache, v_cache = common.walk_cache_groups(
        layer, cfg.layer_indices(), 'smallthinker_layer', x, k_cache, v_cache,
        block_tables, functools.partial(_layer_weights, params),
        lambda kind: (*rope, positions, valid, context_lens, tail_lens),
    )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    last_hidden = common.last_token(hidden, tail_lens)
    return logits(params, cfg, last_hidden)[:, 0], k_cache, v_cache


def _decode_core(
    params, cfg, rope, attn_backend, input_ids, positions, context_lens,
    caches, block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first four arguments are bound; ``caches`` is ``(k_cache, v_cache)``)."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    x = common.embed(params, cfg.dtype, input_ids)  # [B, H]

    def layer(group, rotation, x, lp, mp, banks, mi, k_cache, v_cache, li,
              table, cos, sin, positions, context_lens, live):
        u = _norm(x, lp['ln']['scale'], cfg)
        ranking = _rank(u, mp, banks, cfg, mi)
        q, k, v = (
            t[:, 0] for t in _qkv(
                u[:, None], lp, cfg, rotation, cos, sin, positions[:, None]
            )
        )
        with jax.named_scope(f'distllm.attn_{group}'):
            k_cache, v_cache = write_token_kv(
                k_cache, v_cache, k, v, table, positions, layer=li
            )
            attn = decode_attention(
                q, k_cache, v_cache, table, context_lens, positions,
                backend=attn_backend, sliding_window=cfg.window(group),
                layer=li,
            )
        x, pairs = _finish_layer(
            x, attn, lp, mp, banks, cfg, mi, ranking, live
        )
        return x, k_cache, v_cache, pairs

    x, k_cache, v_cache, pairs = common.walk_cache_groups(
        layer, cfg.layer_indices(), 'smallthinker_layer', x, *caches,
        block_tables, functools.partial(_layer_weights, params),
        lambda kind: (*rope, positions, context_lens, live),
        counts=jnp.zeros((2,), jnp.int32),
    )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    return logits(params, cfg, hidden), (k_cache, v_cache), pairs


def decode_loop(  # distlint: traced
    params: dict,
    cfg: SmallThinkerConfig,
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
    """``mistral.decode_loop``'s contract over the two cache groups, as
    ``laguna.decode_loop``: returns ``(tokens [num_steps, B], k_cache,
    v_cache, last_ids, moe_pairs [2])``."""
    rope = _rope_table(cfg, max_table_positions or cfg.max_position_embeddings)
    tokens, (k_cache, v_cache), ids, pairs = common.decode_window(
        functools.partial(_decode_core, params, cfg, rope, attn_backend),
        input_ids, positions, context_lens, (tuple(k_cache), tuple(v_cache)),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=jnp.zeros((2,), jnp.int32),
    )
    return tokens, k_cache, v_cache, ids, pairs
