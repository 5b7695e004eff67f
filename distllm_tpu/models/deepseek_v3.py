"""DeepSeek-V3-family decoders with no query compression (``model_type
deepseek_v3``: kakaocorp Kanana-2-30B-A3B): multi-head latent attention
(MLA), a dense SwiGLU MLP in the leading layer(s) and, in the others, routed
SwiGLU experts scored by sigmoid with a selection bias (``noaux_tc``) plus
shared experts.

A cached token is ONE row a layer, ``[c (kv_lora_rank) | k_r
(qk_rope_head_dim)]`` after ``kv_a_layernorm`` and after the rotation, and
every query head attends over it: ``cfg.cache_spec()`` declares one latent
paged group (``common.PagedGroup.row``), whose pool is one plane a layer and
has no V plane (values are the row's first ``kv_lora_rank`` lanes). The
planes are a tuple, not the one stacked array a K/V group's pool is: beside
this family's weights a chain of unrolled writes into ONE 4.7 GB array reads
to XLA's rematerialization as two such arrays, over the chip's memory, and it
recomputes the q projection in 22 layers of every prefill (``PERF.md``
section 6, PR 56; the cures tried are there). Both
serving programs attend in the ABSORBED form through the ragged paged
kernel, one shared KV head with ``num_heads`` queries on it::

    h = rms(x);  q = h Wq -> [H, 192] = [q_n (128) | q_r (64)]
    a = h Wa -> [576] = [c_raw (512) | k_r (64)];  c = rms(c_raw; g_kv)
    q_r, k_r = rope(q_r, k_r, pos)       all 64 dims, k_r one head for all H
    row = [c | k_r]                       written to the pool
    qt_h = Wuk_h q_n,h  (R^512)           distllm.attn_latent_proj
    s_h(i, j) = (qt_h(i) . c(j) + q_r,h(i) . k_r(j)) / sqrt(192)
    ot_h = sum_j softmax_j(s_h)(i, j) c(j)      the kernel, distllm.attn_latent
    o_h = Wuv_h^T ot_h;  x = x + concat_h(o_h) Wo

which equals the published expanded form (``[k_n,h | v_h] = c Wb[h]``,
``benchmarks/reference_deepseek_v3.py``). The prefill program takes the same
path with ``num_heads x span`` queries a row: the chip's decision run
(``PERF.md`` section 6, PR 32) put it before the expanded form.

``kv_b_proj``'s columns are stored as their two halves, ``k_up`` (``Wuk``,
``[kv_lora_rank, H * qk_nope_head_dim]``) and ``v_up`` (``Wuv``,
``[kv_lora_rank, H * v_head_dim]``): a sliced half of one kernel would be
copied out every step.

MLP: ``h2 = rms(x)``; dense ``x + (silu(h2 Wg) * (h2 Wu)) Wd``; sparse ``z =
h2 Wr`` (float32), ``s = sigmoid(z)``, ``S = top_k(s + b)`` (``b`` chooses
and never weighs), ``w_e = routed_scaling_factor * s_e / (sum_S s + 1e-20)``,
``x + sum_{e in S} w_e E_e(h2) + E_shared(h2)``, the shared expert one
SwiGLU of width ``n_shared_experts * moe_intermediate_size`` added here,
once. The routed experts are ``models/moe.py``: a chip may hold a share of
them (``first_local_expert``, ``num_local_experts``) while the router ranks
all ``num_experts``.

``rope_interleave`` says how the published checkpoint orders the rotary
columns (interleaved pairs, permuted to the half-split order before
``rotate_half``); on seeded weights the two are one function, so this module
rotates in the half-split order and the permutation belongs to a loader.
There is no ``params_from_hf`` yet.
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
_TREES = ('attn', 'dense', 'sparse')
_SCALES = ('ln', 'kv_ln', 'mlp_ln')  # {'scale': ...}; the rest {'kernel': ...}
_F32_LEAVES = ('router_bias',)  # a buffer of the published code, float32


class DeepseekV3Config(BaseConfig):
    name: Literal['deepseek_v3'] = 'deepseek_v3'
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 6144  # width of a dense layer's MLP
    moe_intermediate_size: int = 768  # width of one routed expert
    n_shared_experts: int = 2
    first_k_dense_replace: int = 1  # the leading layers with a dense MLP
    # The router ranks num_experts; this chip holds num_local_experts of
    # them, ids first_local_expert onward (all of them by default).
    num_experts: int = 128
    num_local_experts: int = 128
    first_local_expert: int = 0
    experts_per_token: int = 6
    routed_scaling_factor: float = 2.448
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768
    dtype: str = 'bfloat16'

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values a cached token holds in a layer: the latent and the one
        rotated key head."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def stored_row(self) -> int:
        return self.cache_spec().paged[0].stored_row

    # What the attention backend's resolution and the engine's messages ask
    # of a decoder: the kernel sees one KV head whose keys are a stored row.
    @property
    def head_size(self) -> int:
        return self.stored_row

    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    def count(self, kind: str) -> int:
        """Layers of a parameter tree."""
        dense = min(self.first_k_dense_replace, self.num_layers)
        return {
            'attn': self.num_layers, 'dense': dense,
            'sparse': self.num_layers - dense,
        }[kind]

    def mlp_of(self, layer: int) -> tuple[str, int]:
        """``(MLP kind, index in its tree)`` of a layer."""
        dense = self.count('dense')
        return ('dense', layer) if layer < dense else ('sparse', layer - dense)

    def cache_spec(self) -> common.CacheSpec:
        """One latent group over every layer: rows of ``kv_lora_rank +
        qk_rope_head_dim``, values their first ``kv_lora_rank`` lanes."""
        return common.CacheSpec(
            paged=(common.PagedGroup(
                'latent', self.num_layers, None,
                row=self.latent_row, value_lanes=self.kv_lora_rank,
            ),),
            programs=__name__,
            program_prefix='deepseek_',
            dense_prefill=False,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'DeepseekV3Config':
        """The published keys as they are, plus two that state a chip's
        share as ``granitemoehybrid``'s and ``laguna``'s configurations do
        (``num_routed_experts``: the router's width where
        ``n_routed_experts`` counts the experts held;
        ``first_local_expert``). Values this module does not implement are
        refused by name."""
        refusals = (
            ('q_lora_rank', hf.get('q_lora_rank') is not None,
             'query compression (q_a_proj, q_a_layernorm, q_b_proj)'),
            ('rope_scaling', hf.get('rope_scaling') is not None,
             'a scaled rotation (YaRN) of the rope part'),
            ('n_group', hf.get('n_group', 1) != 1, 'group-limited routing'),
            ('topk_group', hf.get('topk_group', 1) != 1,
             'group-limited routing'),
            ('scoring_func', hf.get('scoring_func', 'sigmoid') != 'sigmoid',
             'a router score other than sigmoid'),
            ('topk_method', hf.get('topk_method', 'noaux_tc') != 'noaux_tc',
             'a selection other than noaux_tc'),
            ('norm_topk_prob', not hf.get('norm_topk_prob', True),
             'kept weights left unnormalised'),
            ('moe_layer_freq', hf.get('moe_layer_freq', 1) != 1,
             'dense layers between the sparse ones'),
            ('attention_bias', bool(hf.get('attention_bias', False)),
             'projection biases'),
            ('tie_word_embeddings', bool(hf.get('tie_word_embeddings', False)),
             'a tied output head'),
            ('hidden_act', hf.get('hidden_act', 'silu') != 'silu',
             'an activation other than silu'),
            ('qk_rope_head_dim', hf['qk_rope_head_dim'] % 2 != 0,
             'an odd rotary width'),
            ('kv_lora_rank', hf['kv_lora_rank'] % 128 != 0,
             'a latent that is not whole 128-lane tiles (the values are '
             'read as leading lanes of the cached row)'),
        )
        for key, refused, what in refusals:
            if refused:
                raise ValueError(
                    f'deepseek_v3: {key}={hf.get(key)!r} is not implemented '
                    f'({what})'
                )
        held = hf['n_routed_experts']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_layers=hf['num_hidden_layers'],
            num_heads=hf['num_attention_heads'],
            qk_nope_head_dim=hf['qk_nope_head_dim'],
            qk_rope_head_dim=hf['qk_rope_head_dim'],
            v_head_dim=hf['v_head_dim'],
            kv_lora_rank=hf['kv_lora_rank'],
            intermediate_size=hf['intermediate_size'],
            moe_intermediate_size=hf['moe_intermediate_size'],
            n_shared_experts=hf['n_shared_experts'],
            first_k_dense_replace=hf.get('first_k_dense_replace', 0),
            num_experts=hf.get('num_routed_experts', held),
            num_local_experts=held,
            first_local_expert=hf.get('first_local_expert', 0),
            experts_per_token=hf['num_experts_per_tok'],
            routed_scaling_factor=hf.get('routed_scaling_factor', 1.0),
            rope_theta=float(hf.get('rope_theta', 10000.0)),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-6),
            max_position_embeddings=hf.get('max_position_embeddings', 32768),
        )


# ------------------------------------------------------------- parameters
def _tree_shapes(cfg: DeepseekV3Config, kind: str) -> dict:
    """``name -> shape`` of one layer's parameters in the tree ``kind``
    (kernels ``[in, out]``)."""
    h, heads = cfg.hidden_size, cfg.num_heads
    if kind == 'attn':
        return {
            'ln': (h,), 'q': (h, heads * cfg.qk_head_dim),
            'kv_a': (h, cfg.latent_row), 'kv_ln': (cfg.kv_lora_rank,),
            'k_up': (cfg.kv_lora_rank, heads * cfg.qk_nope_head_dim),
            'v_up': (cfg.kv_lora_rank, heads * cfg.v_head_dim),
            'o': (heads * cfg.v_head_dim, h),
        }
    if kind == 'dense':
        i = cfg.intermediate_size
        return {'mlp_ln': (h,), 'gate': (h, i), 'up': (h, i), 'down': (i, h)}
    i, e = cfg.moe_intermediate_size, cfg.num_local_experts
    s = cfg.n_shared_experts * i
    return {
        'mlp_ln': (h,), 'router': (h, cfg.num_experts),
        'router_bias': (cfg.num_experts,),  # e_score_correction_bias
        'gate': (e, h, i), 'up': (e, h, i), 'down': (e, i, h),
        'shared_gate': (h, s), 'shared_up': (h, s), 'shared_down': (s, h),
    }


def _wrap(name: str, leaf):
    return {'scale' if name in _SCALES else 'kernel': leaf}


def _top_shapes(cfg: DeepseekV3Config) -> dict:
    return {
        'embed': (cfg.vocab_size, cfg.hidden_size),
        'lm_head': (cfg.hidden_size, cfg.vocab_size),
    }


def _trees(cfg: DeepseekV3Config) -> dict:
    return common.tree_table(
        _TREES, cfg.count, lambda kind: _tree_shapes(cfg, kind)
    )


def init_on_device(rng: jax.Array, cfg: DeepseekV3Config) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels, unit norm scales, the router's selection bias normal(0,
    0.02) in float32 (a zero buffer in the published code before training),
    one RNG call per parameter kind."""

    def leaf(name, key, shape, normal):
        return normal(key, shape, dtype=F32) if name in _F32_LEAVES else None

    return common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size, _top_shapes(cfg), _trees(cfg), _wrap,
        _SCALES, leaf,
    )


def param_specs(cfg: DeepseekV3Config, params: dict | None = None) -> dict:
    """Expert banks over ``expert``, everything else replicated."""
    return common.tree_specs(
        _top_shapes(cfg), _trees(cfg), _wrap, [('sparse', n) for n in _BANKS]
    )


def params_from_hf(state: dict, cfg: DeepseekV3Config) -> dict:
    raise NotImplementedError(
        'deepseek_v3: no converter from a published checkpoint yet (it has '
        'to split kv_b_proj into k_up and v_up a head, stack the experts '
        'into banks and undo rope_interleave\'s column order); serve seeded '
        'weights (init_on_device)'
    )


# The attention kernels that the serving programs read a layer an array:
# the decode window walks its layers unrolled, and the compiler merges the
# 24 static slices of each of these three stacks into fusions that write
# every layer's kernel out again each step (805 MB read and nearly all of it
# written at the cell's depth, 2.1 ms of a 24.1 ms step; ``PERF.md`` section
# 6, PR 51). ``o`` and ``kv_a`` are sliced the same way and fold into their
# dots; ``tests/test_aot_tpu.py`` holds every window's text to the rule.
_PER_LAYER = ('q', 'k_up', 'v_up')


def serving_params(params: dict, own: bool = False) -> dict:
    """The tree the serving programs read, of the public tree
    (``init_on_device``'s, ``param_specs``'): the same arrays, with each
    stack of ``_PER_LAYER`` a tuple of its layers (``common.unstack``), so
    that ``common.layer_at`` hands a layer's kernel on and no program
    slices one. The engine calls this once, before it compiles; ``own``
    (the engine owns ``params``) deletes each stack as its layers stand,
    without it the caller's stacks live on beside them (805 MB at the
    cell's widths). ``params`` itself is not changed."""
    attn = dict(params['attn'])
    for name in _PER_LAYER:
        attn[name] = {'kernel': common.unstack(attn[name]['kernel'], own)}
    return {**params, 'attn': attn}


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.rms_norm_eps)


def _rope_tables(cfg: DeepseekV3Config, max_len: int):
    cos, sin = common.rope_frequencies(
        cfg.qk_rope_head_dim, max_len, cfg.rope_theta
    )
    return jnp.asarray(cos), jnp.asarray(sin)


def _latent_parts(normed, lp, cfg, cos, sin, positions):
    """``normed [B, S, hidden]`` -> ``(q_n [B, S, H, 128], q_r [B, S, H,
    64], row [B, S, 1, stored_row])``: the queries' two parts, the rope part
    rotated, and the tokens' cache rows ``[c | k_r | 0..]`` (the latent
    normed, the one key head rotated, zeros up to whole lane tiles)."""
    b, s, _ = normed.shape
    q = common.dense(normed, lp['q']['kernel']).reshape(
        b, s, cfg.num_heads, cfg.qk_head_dim
    )
    q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    a = common.dense(normed, lp['kv_a']['kernel'])
    c = _norm(a[..., :cfg.kv_lora_rank], lp['kv_ln']['scale'], cfg)
    k_r = common.apply_rope(
        a[..., None, cfg.kv_lora_rank:], cos, sin, positions
    )
    q_r = common.apply_rope(q_r, cos, sin, positions)
    pad = cfg.stored_row - cfg.latent_row
    row = jnp.concatenate(
        [c[..., None, :], k_r, jnp.zeros((b, s, 1, pad), c.dtype)], axis=-1
    )
    return q_n, q_r, row


def _absorb_queries(q_n, q_r, lp, cfg):
    """``qt_h = Wuk_h q_n,h`` beside the rotated part and the pad: the
    kernel's queries ``[B, S, H, stored_row]``."""
    with jax.named_scope('distllm.attn_latent_proj'):
        k_up = lp['k_up']['kernel'].reshape(
            cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
        )
        qt = jnp.einsum('bshd,chd->bshc', q_n, k_up.astype(q_n.dtype))
        pad = cfg.stored_row - cfg.latent_row
        return jnp.concatenate(
            [qt, q_r, jnp.zeros((*q_r.shape[:-1], pad), qt.dtype)], axis=-1
        )


def _attn_out(ot, lp, cfg):
    """``o_h = Wuv_h^T ot_h``, then the output projection."""
    with jax.named_scope('distllm.attn_latent_proj'):
        v_up = lp['v_up']['kernel'].reshape(
            cfg.kv_lora_rank, cfg.num_heads, cfg.v_head_dim
        )
        o = jnp.einsum('bshc,chd->bshd', ot, v_up.astype(ot.dtype))
    return common.dense(
        o.reshape(*o.shape[:2], cfg.num_heads * cfg.v_head_dim),
        lp['o']['kernel'],
    )


def _mlp(x, mp, cfg, mlp_kind, counted, banks, mi):
    """The MLP block of one layer for ``x [T, H]`` (already normed);
    returns it and the layer's (routed, held) pair counts. ``banks`` is the
    sparse tree: the expert banks stay stacked, ``mi`` picks the layer
    inside the expert matmuls (``models/moe.py``)."""
    if mlp_kind == 'dense':
        return common.dense_mlp(x, mp), jnp.zeros((2,), jnp.int32)
    routed, pairs = routed_experts(
        x, mp['router']['kernel'], *(banks[n]['kernel'] for n in _BANKS),
        cfg.experts_per_token, first_expert=cfg.first_local_expert,
        counted=counted, layer=mi, routed_scale=cfg.routed_scaling_factor,
        scoring='sigmoid', select_bias=mp['router_bias']['kernel'],
    )
    # The shared experts: every chip of the expert axis computes them alike,
    # so they are counted once, here, whatever share of the bank is held.
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


def logits(params: dict, cfg: DeepseekV3Config, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; the untied head over the held
    slice of the vocabulary."""
    return common.dense(hidden, params['lm_head']).astype(F32)


def _mlp_layer_at(params, mlp_kind, mi):
    return common.layer_at(
        params[mlp_kind], mi, skip=_BANKS if mlp_kind == 'sparse' else ()
    )


def _mlp_kinds(cfg: DeepseekV3Config) -> list[tuple[str]]:
    """The kinds of layer ``common.once_a_kind`` jits: one an MLP kind."""
    return [(kind,) for kind in ('dense', 'sparse') if cfg.count(kind)]


# ----------------------------------------------------------------- forwards
def prefill_paged(  # distlint: traced
    params: dict,
    cfg: DeepseekV3Config,
    input_ids: jnp.ndarray,  # [B, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache,  # per layer [num_blocks, block_size, stored_row]
    v_cache,  # (): a latent pool has no V plane
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    max_table_positions: int | None = None,
    attn_backend: str = 'xla',
):
    """One span of every row through the paged path: a whole prompt, or one
    chunk of a long one. Each layer writes the span's latent rows into its
    plane first, then the span's queries attend over the pages in the
    absorbed form. Returns ``(last_logits [B, V] float32, k_cache,
    v_cache)``. The layers are walked unrolled: each layer's plane is a
    buffer of its own."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention,
        write_chunk_kv,
    )

    s = input_ids.shape[1]
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    cos, sin = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    planes = list(k_cache)
    x = common.embed(params, cfg.dtype, input_ids)

    def layer(mlp_kind, x, lp, mp, banks, mi, plane, table, cos, sin,
              positions, valid, context_lens, tail_lens):
        normed = _norm(x, lp['ln']['scale'], cfg)
        q_n, q_r, row = _latent_parts(normed, lp, cfg, cos, sin, positions)
        q = _absorb_queries(q_n, q_r, lp, cfg)
        with jax.named_scope('distllm.attn_latent'):
            plane, _ = write_chunk_kv(
                plane, None, row, None, table, positions, valid
            )
            ot = ragged_paged_attention(
                q, plane, None, table, context_lens, positions,
                q_lens=tail_lens, scale=cfg.softmax_scale,
                backend=attn_backend, value_lanes=cfg.kv_lora_rank,
            )
        x, _ = _finish_layer(
            x, _attn_out(ot, lp, cfg), mp, cfg, mlp_kind, valid, banks, mi,
        )
        return x, plane

    layer_of = common.once_a_kind(layer, _mlp_kinds(cfg), 'deepseek_layer')
    for li in range(cfg.num_layers):
        mlp_kind, mi = cfg.mlp_of(li)
        x, planes[li] = layer_of[(mlp_kind,)](
            x, common.layer_at(params['attn'], li),
            _mlp_layer_at(params, mlp_kind, mi), params.get('sparse'),
            jnp.int32(mi), planes[li], block_tables, cos, sin, positions,
            valid, context_lens, tail_lens,
        )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    last_hidden = common.last_token(hidden, tail_lens)
    return logits(params, cfg, last_hidden)[:, 0], tuple(planes), ()


def _decode_core(
    params, cfg, rope, attn_backend, input_ids, positions, context_lens,
    caches, block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first four arguments are bound; ``caches`` is ``(planes,)``). The layers
    are walked unrolled, each with static indices: a static slice of a
    stacked kernel folds into its matmul, the kernels of ``_PER_LAYER`` are
    a layer an array in the serving form, and a layer's plane is written in
    place."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    x = common.embed(params, cfg.dtype, input_ids)  # [B, H]
    planes = list(caches[0])
    pairs = jnp.zeros((2,), jnp.int32)

    def layer(mlp_kind, x, lp, mp, banks, mi, plane, table, cos, sin,
              positions, context_lens, live):
        normed = _norm(x, lp['ln']['scale'], cfg)
        q_n, q_r, row = _latent_parts(
            normed[:, None], lp, cfg, cos, sin, positions[:, None]
        )
        q = _absorb_queries(q_n, q_r, lp, cfg)  # [B, 1, H, stored_row]
        with jax.named_scope('distllm.attn_latent'):
            plane, _ = write_token_kv(
                plane, None, row[:, 0], None, table, positions
            )
            ot = decode_attention(
                q[:, 0], plane, None, table, context_lens, positions,
                backend=attn_backend, scale=cfg.softmax_scale,
                value_lanes=cfg.kv_lora_rank,
            )[:, None]
        x, layer_pairs = _finish_layer(
            x, _attn_out(ot, lp, cfg)[:, 0], mp, cfg, mlp_kind, live, banks, mi,
        )
        return x, plane, layer_pairs

    layer_of = common.once_a_kind(layer, _mlp_kinds(cfg), 'deepseek_layer')
    for li in range(cfg.num_layers):
        mlp_kind, mi = cfg.mlp_of(li)
        x, planes[li], layer_pairs = layer_of[(mlp_kind,)](
            x, common.layer_at(params['attn'], li),
            _mlp_layer_at(params, mlp_kind, mi), params.get('sparse'),
            jnp.int32(mi), planes[li], block_tables, *rope, positions,
            context_lens, live,
        )
        pairs = pairs + layer_pairs
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    return logits(params, cfg, hidden), (tuple(planes),), pairs


def decode_loop(  # distlint: traced
    params: dict,
    cfg: DeepseekV3Config,
    input_ids: jnp.ndarray,  # [B] last emitted token per slot
    positions: jnp.ndarray,  # [B]
    k_cache,  # per layer [num_blocks, block_size, stored_row]
    v_cache,  # ()
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
):
    """``mistral.decode_loop``'s contract over the latent pool. A row out
    of budget writes its row to the trash block. Returns ``(tokens
    [num_steps, B], k_cache, v_cache, last_ids, moe_pairs [2])``, the last
    being the window's (routed, held) pair counts over the rows and steps
    that ran."""
    del v_cache  # no V plane
    rope = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    tokens, (planes,), ids, pairs = common.decode_window(
        functools.partial(_decode_core, params, cfg, rope, attn_backend),
        input_ids, positions, context_lens, (tuple(k_cache),),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=jnp.zeros((2,), jnp.int32),
    )
    return tokens, planes, (), ids, pairs
