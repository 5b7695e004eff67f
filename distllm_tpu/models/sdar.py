"""SDAR block-diffusion decoders with routed experts (``model_type sdar_moe``:
JetLM SDAR-30B-A3B-Chat, "SDAR: A Synergistic Diffusion-AutoRegression
Paradigm for Scalable Sequence Generation", arXiv:2510.06303): the Qwen3-MoE
block under a BLOCK-CAUSAL mask, and generation by diffusion over blocks of
``block_length`` positions.

The layer, for ``T`` token rows at positions ``p``, ``d = head_dim``::

    u = rms(x; g1);  q = u Wq [N, d];  k = u Wk [G, d];  v = u Wv [G, d]
    q = rms(q; gq);  k = rms(k; gk)      over each head's d lanes (gq, gk [d])
    q, k = rope(q, k, p)                 theta, rotate-half, the whole head
    a = softmax(q k^T / sqrt(d) + mask) v;  key j visible iff
        j < (p // B + 1) * B             the end of the query's block
    x' = x + a Wo;  h = rms(x'; g2);  r = h Wr in float32 over E experts
    S = the k largest of r;  g_e = softmax over S of r_e   (norm_topk_prob)
    x_next = x' + sum_{e in S} g_e (silu(h G_e) * (h U_e)) D_e

After the layers the final norm and the untied head. The logits at a masked
position are the distribution of the token AT that position (no shift).

The loop (``decode_loop``), block length ``B``, ``S`` denoise steps: a prompt
of ``P`` tokens is prefilled over its ``P // B`` whole blocks (``prefill_paged``
under the mask above) and yields no token; its last ``P mod B`` tokens are
the given positions of the first block at ``[L, L + B)``, ``L = (P // B) *
B``, the rest of it the mask token; for ``s = 0 .. S - 1`` one forward of the
block's ``B`` positions over the cache ``[0, L)`` and the block itself gives
every position a candidate and a confidence (``ops.sampling.
sample_tokens_confidence``) and ``ops.sampling.select_unmask`` decides ``n_s =
B // S + (s < B mod S)`` of the masked positions, more under a threshold;
then ONE forward of the decided block writes the K/V that stay, and ``L +=
B``. Whether a position is masked is a FLAG, never read off ``id ==
mask_token_id``: a prompt or a sampled token that equals the mask id is a
token like any other (the published loop reads it off the ids).

A denoise forward writes the block's K/V into the row's own pages
(``write_chunk_kv``; the commit overwrites them) and attends through
``decode_attention``: all ``B`` queries of a row's block see the same keys
``[0, L + B)``, so the block folds into the GQA group (``B x N / G`` queries a
KV head) and a row is ONE decode row of the paged kernel's row walk.

A chip may hold a share of the experts (``first_local_expert``,
``num_local_experts``) while the router ranks all ``num_experts``
(``models/moe.py``); nothing stands in for absent chips. The layers are all
alike: every program scans them rolled over the stacked trees and pool, the
banks whole with a traced ``layer``.
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import common
from distllm_tpu.models.moe import routed_experts
from distllm_tpu.utils import BaseConfig

F32 = jnp.float32
_BANKS = ('gate', 'up', 'down')
_SCALES = ('attn_ln', 'mlp_ln', 'q_norm', 'k_norm')


class SdarConfig(BaseConfig):
    name: Literal['sdar_moe'] = 'sdar_moe'
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768  # width of one expert
    # The router ranks num_experts; this chip holds num_local_experts of
    # them, ids first_local_expert onward (all of them by default).
    num_experts: int = 128
    num_local_experts: int = 128
    first_local_expert: int = 0
    experts_per_token: int = 8
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768
    # Generation: positions decided together, and the id a masked position
    # is embedded as (neither is a key of the published config.json).
    block_length: int = 4
    mask_token_id: int = 151669
    dtype: str = 'bfloat16'

    @property
    def head_size(self) -> int:
        return self.head_dim

    def cache_spec(self) -> common.CacheSpec:
        """One full-context K/V group under one block table, this module's
        programs, no dense prefill, and the block a sequence decides
        together: what the engine reads budgets, page reserves, "prefill
        yields no token" and the prompt's given remainder from."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_layers),),
            programs=__name__,
            program_prefix='sdar_',
            dense_prefill=False,
            block=self.block_length,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'SdarConfig':
        """The published keys as they are, ``block_length`` and
        ``mask_token_id`` beside them, and two that state a chip's share
        (``num_routed_experts``: the router's width where ``num_experts``
        counts the experts held; ``first_local_expert``). Values this module
        does not implement are refused by name."""
        refusals = (
            ('use_sliding_window', bool(hf.get('use_sliding_window', False)),
             'a sliding window'),
            ('rope_scaling', hf.get('rope_scaling') is not None,
             'a scaled rotation'),
            ('mlp_only_layers', bool(hf.get('mlp_only_layers')),
             'a layer with a dense MLP'),
            ('decoder_sparse_step', int(hf.get('decoder_sparse_step', 1)) != 1,
             'a layer with a dense MLP'),
            ('attention_bias', bool(hf.get('attention_bias', False)),
             'projection biases'),
            ('norm_topk_prob', not hf.get('norm_topk_prob', True),
             'gates that are not normalised over the kept'),
            ('tie_word_embeddings', bool(hf.get('tie_word_embeddings', False)),
             'a head tied to the embedding'),
            ('hidden_act', hf.get('hidden_act', 'silu') != 'silu',
             'another activation than silu'),
        )
        for key, refused, what in refusals:
            if refused:
                raise ValueError(
                    f'sdar_moe: {key}={hf.get(key)!r} is not implemented '
                    f'({what})'
                )
        heads, held = hf['num_attention_heads'], hf['num_experts']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_layers=hf['num_hidden_layers'],
            num_heads=heads,
            num_kv_heads=hf.get('num_key_value_heads', heads),
            head_dim=hf.get('head_dim') or hf['hidden_size'] // heads,
            moe_intermediate_size=hf['moe_intermediate_size'],
            num_experts=hf.get('num_routed_experts', held),
            num_local_experts=held,
            first_local_expert=hf.get('first_local_expert', 0),
            experts_per_token=hf['num_experts_per_tok'],
            rope_theta=float(hf.get('rope_theta', 1e6)),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-6),
            max_position_embeddings=hf.get('max_position_embeddings', 32768),
            block_length=int(hf.get('block_length', 4)),
            mask_token_id=int(hf.get('mask_token_id', 151669)),
        )


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: SdarConfig) -> dict:
    """``name -> shape`` of one layer's parameters (kernels ``[in, out]``)."""
    h, d = cfg.hidden_size, cfg.head_dim
    q_out, kv_out = cfg.num_heads * d, cfg.num_kv_heads * d
    i, e = cfg.moe_intermediate_size, cfg.num_local_experts
    return {
        'attn_ln': (h,), 'q': (h, q_out), 'k': (h, kv_out), 'v': (h, kv_out),
        'o': (q_out, h), 'q_norm': (d,), 'k_norm': (d,),
        'mlp_ln': (h,), 'router': (h, cfg.num_experts),
        'gate': (e, h, i), 'up': (e, h, i), 'down': (e, i, h),
    }


def _wrap(name: str, leaf):
    return {'scale' if name in _SCALES else 'kernel': leaf}


def _top_shapes(cfg: SdarConfig) -> dict:
    return {
        'embed': (cfg.vocab_size, cfg.hidden_size),
        'head': (cfg.hidden_size, cfg.vocab_size),
    }


def _trees(cfg: SdarConfig) -> dict:
    return common.tree_table(
        ('layers',), lambda tree: cfg.num_layers, lambda tree: _layer_shapes(cfg)
    )


def init_on_device(rng: jax.Array, cfg: SdarConfig) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels, unit norm scales (the layers' and the heads')."""
    return common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size, _top_shapes(cfg), _trees(cfg), _wrap,
        _SCALES,
    )


def param_specs(cfg: SdarConfig, params: dict | None = None) -> dict:
    """Expert banks over ``expert``, everything else replicated (the engine
    refuses a mesh for a model that declares a block: the specs are for the
    tree's shape alone)."""
    return common.tree_specs(
        _top_shapes(cfg), _trees(cfg), _wrap, [('layers', n) for n in _BANKS]
    )


def params_from_hf(state: dict, cfg: SdarConfig) -> dict:
    """The published checkpoint's tensors (``model.layers.N.self_attn.
    {q,k,v,o}_proj``, ``self_attn.{q,k}_norm``, ``input_layernorm``,
    ``post_attention_layernorm``, ``mlp.gate`` and ``mlp.experts.E.
    {gate,up,down}_proj``; ``nn.Linear`` weights ``[out, in]``) as this
    module's tree, the experts held here stacked. The names are the
    Qwen3-MoE block's, with no checkpoint at hand to read where this was
    written: a name that is not there is refused by name, never guessed
    around."""

    def take(name):
        if name not in state:
            raise KeyError(
                f'sdar_moe: the checkpoint has no tensor {name!r}; '
                'params_from_hf knows the published names alone'
            )
        return np.asarray(state[name])

    def kernel(name):
        return np.ascontiguousarray(take(f'{name}.weight').T)

    first, held = cfg.first_local_expert, cfg.num_local_experts
    layers = []
    for layer in range(cfg.num_layers):
        at = f'model.layers.{layer}'
        layers.append({
            'attn_ln': {'scale': take(f'{at}.input_layernorm.weight')},
            'mlp_ln': {'scale': take(f'{at}.post_attention_layernorm.weight')},
            'q_norm': {'scale': take(f'{at}.self_attn.q_norm.weight')},
            'k_norm': {'scale': take(f'{at}.self_attn.k_norm.weight')},
            **{
                n: {'kernel': kernel(f'{at}.self_attn.{n}_proj')}
                for n in ('q', 'k', 'v', 'o')
            },
            'router': {'kernel': kernel(f'{at}.mlp.gate')},
            **{
                n: {'kernel': np.stack([
                    kernel(f'{at}.mlp.experts.{e}.{n}_proj')
                    for e in range(first, first + held)
                ])}
                for n in _BANKS
            },
        })
    return {
        'embed': take('model.embed_tokens.weight'),
        'head': kernel('lm_head'),
        'final_ln': {'scale': take('model.norm.weight')},
        'layers': common.stack_layers(layers),
    }


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.rms_norm_eps)


def _rope_table(cfg: SdarConfig, max_len: int):
    cos, sin = common.rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def _qkv(u, lp, cfg, cos, sin, positions):
    """``(q [R, S, N, d], k, v [R, S, G, d])`` of ``u [R, S, H]``: the
    projections, the RMS norm over each head's lanes of ``q`` and of ``k``,
    then the rotation at ``positions [R, S]``."""
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_dim)  # noqa: E731
    q = heads(common.dense(u, lp['q']['kernel']), cfg.num_heads)
    k = heads(common.dense(u, lp['k']['kernel']), cfg.num_kv_heads)
    v = heads(common.dense(u, lp['v']['kernel']), cfg.num_kv_heads)
    q = _norm(q, lp['q_norm']['scale'], cfg)
    k = _norm(k, lp['k_norm']['scale'], cfg)
    q = common.apply_rope(q, cos, sin, positions)
    k = common.apply_rope(k, cos, sin, positions)
    return q, k, v


def _finish_layer(x, attn, lp, banks, cfg, li, counted):
    """The output projection and its residual, then the routed experts over
    the post-attention norm's rows."""

    def experts(rows, of_rows):
        return routed_experts(
            rows, lp['router']['kernel'], *(banks[n]['kernel'] for n in _BANKS),
            cfg.experts_per_token, first_expert=cfg.first_local_expert,
            counted=of_rows, layer=li,
        )

    mixed = common.dense(
        attn.reshape(*attn.shape[:-2], cfg.num_heads * cfg.head_dim),
        lp['o']['kernel'],
    )
    return common.finish_layer(x, mixed, lp, cfg.rms_norm_eps, experts, counted)


def _layers_xs(params, cfg):
    """The layer scan's xs: every stacked leaf but the banks (which stay
    whole: a layer sliced out of a bank would be copied) and the layer's
    index."""
    small = {n: t for n, t in params['layers'].items() if n not in _BANKS}
    return small, jnp.arange(cfg.num_layers, dtype=jnp.int32)


def block_mask(positions, block_length: int):
    """``[..., S, S]`` bool: key ``j`` visible to the query at ``positions[i]``
    iff ``positions[j]`` lies before the end of the query's block."""
    ceiling = (positions // block_length + 1) * block_length
    return positions[..., None, :] < ceiling[..., :, None]


def logits(params: dict, cfg: SdarConfig, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; the untied head."""
    with jax.named_scope('distllm.head'):
        return common.dense(hidden, params['head']).astype(F32)


# ----------------------------------------------------------------- forwards
def apply(  # distlint: traced
    params: dict,
    cfg: SdarConfig,
    input_ids: jnp.ndarray,  # [R, S], right-padded
    attention_mask: jnp.ndarray,  # [R, S]
) -> jnp.ndarray:
    """Dense forward under the block-causal mask, no cache: ``[R, S]`` ->
    final-normed hidden states ``[R, S, H]``."""
    b, s = input_ids.shape
    valid = attention_mask.astype(bool)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = block_mask(positions, cfg.block_length)[:, None] & valid[:, None, None, :]
    cos, sin = _rope_table(cfg, s)
    x = common.embed(params, cfg.dtype, input_ids)

    def layer(x, xs):
        lp, li = xs
        u = _norm(x, lp['attn_ln']['scale'], cfg)
        q, k, v = _qkv(u, lp, cfg, cos, sin, positions)
        with jax.named_scope('distllm.attn_full'):
            attn = common.sdpa(q, k, v, mask=mask)
        x, _ = _finish_layer(x, attn, lp, params['layers'], cfg, li, valid)
        return x, None

    x, _ = jax.lax.scan(layer, x, _layers_xs(params, cfg))
    return _norm(x, params['final_ln']['scale'], cfg)


def _span_pass(  # distlint: traced
    params, cfg, rope, attend, input_ids, positions, k_cache, v_cache,
    block_tables, valid, counted,
):
    """One span of every row through the pages: each layer writes the span's
    K/V into its plane of the stacked pool, then ``attend(q, k_cache,
    v_cache, layer)`` reads the pages. Returns ``(final-normed hidden [R, S,
    H], k_cache, v_cache, pairs [2])``."""
    from distllm_tpu.ops.paged_attention import write_chunk_kv

    cos, sin = rope
    x = common.embed(params, cfg.dtype, input_ids)

    def layer(carry, xs):
        x, k_cache, v_cache, pairs = carry
        lp, li = xs
        u = _norm(x, lp['attn_ln']['scale'], cfg)
        q, k, v = _qkv(u, lp, cfg, cos, sin, positions)
        with jax.named_scope('distllm.attn_full'):
            k_cache, v_cache = write_chunk_kv(
                k_cache, v_cache, k, v, block_tables, positions, valid,
                layer=li,
            )
            attn = attend(q, k_cache, v_cache, li)
        x, layer_pairs = _finish_layer(
            x, attn, lp, params['layers'], cfg, li, counted
        )
        return (x, k_cache, v_cache, pairs + layer_pairs), None

    (x, k_cache, v_cache, pairs), _ = jax.lax.scan(
        layer, (x, k_cache, v_cache, jnp.zeros((2,), jnp.int32)),
        _layers_xs(params, cfg),
    )
    return _norm(x, params['final_ln']['scale'], cfg), k_cache, v_cache, pairs


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: SdarConfig,
    input_ids: jnp.ndarray,  # [R, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [R, S] absolute positions
    k_cache: jnp.ndarray,  # [L, num_blocks, block_size, N_kv * Hd]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [R, max_blocks]
    context_lens: jnp.ndarray,  # [R] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [R] valid tokens in input_ids (0 = pad row)
    max_table_positions: int | None = None,
    attn_backend: str = 'xla',
    all_logits: bool = False,
):
    """One span of whole blocks of every row through the pages under the
    block-causal mask (``mistral.prefill_paged``'s operands; the span
    schedule's static ``block_length``). Returns ``(last_logits [R, V]
    float32, k_cache, v_cache)``: a prefill decides nothing, and the engine
    samples nothing from it (``all_logits``: ``[R, S, V]``, for the tests)."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention

    s = input_ids.shape[1]
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    rope = _rope_table(cfg, max_table_positions or cfg.max_position_embeddings)

    def attend(q, k_cache, v_cache, li):
        return ragged_paged_attention(
            q, k_cache, v_cache, block_tables, context_lens, positions,
            q_lens=tail_lens, backend=attn_backend, layer=li,
            block_length=cfg.block_length,
        )

    hidden, k_cache, v_cache, _ = _span_pass(
        params, cfg, rope, attend, input_ids, positions, k_cache, v_cache,
        block_tables, valid, valid,
    )
    if not all_logits:
        hidden = common.last_token(hidden, tail_lens)[:, 0]
    return logits(params, cfg, hidden), k_cache, v_cache


def _block_pass(  # distlint: traced
    params, cfg, rope, attn_backend, ids, start, k_cache, v_cache,
    block_tables, live,
):
    """One forward of every row's block ``ids [R, B]`` at positions ``start +
    [0, B)`` over the cache ``[0, start)`` and the block itself: the block's
    K/V written into the row's pages, the block folded into the GQA group
    (``[R, G * B * N / G, d]``: one decode row a block, every query seeing
    ``[0, start + B)``). A row that is not ``live`` has a zeroed table (its
    writes go to the trash block) and is left out of the counts."""
    from distllm_tpu.ops.paged_attention import decode_attention

    b = cfg.block_length
    rows = ids.shape[0]
    positions = start[:, None] + jnp.arange(b, dtype=start.dtype)[None, :]
    valid = jnp.broadcast_to(live[:, None], positions.shape)
    seen = start + b
    nkv, group, d = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim

    def attend(q, k_cache, v_cache, li):
        folded = q.reshape(rows, b, nkv, group, d).transpose(0, 2, 1, 3, 4)
        out = decode_attention(
            folded.reshape(rows, nkv * b * group, d), k_cache, v_cache,
            block_tables, seen, seen - 1, backend=attn_backend, layer=li,
        )
        out = out.reshape(rows, nkv, b, group, d).transpose(0, 2, 1, 3, 4)
        return out.reshape(rows, b, cfg.num_heads, d)

    return _span_pass(
        params, cfg, rope, attend, ids, positions, k_cache, v_cache,
        block_tables, valid, valid,
    )


def unmask_schedule(block_length: int, steps: int, s):
    """Positions the schedule decides at denoise step ``s`` of ``steps``:
    ``B // S + (s < B mod S)``."""
    return block_length // steps + (s < block_length % steps)


def decode_loop(  # distlint: traced
    params: dict,
    cfg: SdarConfig,
    input_ids: jnp.ndarray,  # [R, B]: the given tokens of a row's first block
    positions: jnp.ndarray,  # [R] context_lens - 1 (as mistral's; not read)
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [R, max_blocks]: covers the window's blocks
    context_lens: jnp.ndarray,  # [R] tokens the row has (prompt and decided)
    steps_left: jnp.ndarray,  # [R] int32: positions to cover, whole blocks
    temperature: jnp.ndarray,  # [R]
    top_p: jnp.ndarray,  # [R]
    min_p: jnp.ndarray,  # [R]
    top_k: jnp.ndarray,  # [R] int32 (0 disables)
    seeds: jnp.ndarray,  # [R] uint32 per-request sampling seeds
    num_steps: int,
    attn_backend: str = 'xla',
    max_table_positions: int | None = None,
    sampling_top_window: int = 0,
    denoise_steps: int | None = None,
    unmask_threshold: jnp.ndarray | None = None,  # [R] float32
    return_logits: bool = False,
):
    """``num_steps // B`` blocks of every row in ONE dispatch, with the
    operands ``mistral.decode_loop`` documents. A row's ``context_lens``
    tokens are in the cache up to ``L = context_lens // B * B``; the other
    ``context_lens - L`` are the first ``input_ids`` of its row, the given
    positions of its first block. A row covers ``steps_left`` positions
    (whole blocks; 0: the row is dead and writes to the trash block).

    ``denoise_steps`` (``S``, static; None: ``B``, a position a step) and
    ``unmask_threshold`` (a row's ``tau``; None or >= 1: the static rule)
    are the loop's two settings. The draw at position ``p`` in denoise step
    ``s`` uses the key of counter ``p * S + s`` of the row's seed.

    Returns ``(tokens [num_steps, R], k_cache, v_cache, input_ids,
    counters)``: row ``i`` of ``tokens`` is position ``L + i`` (a given
    position reads its given token); ``counters`` is a dict of int32:
    ``decided_at [num_steps, R]`` (the denoise step a position was decided
    at, -1 a given one), ``forwards`` (a live row's block through one
    forward: ``S + 1`` a block), ``blocks`` (live row blocks), ``decided``
    (positions decided in them), ``moe_pairs`` and ``moe_pairs_held``.
    ``return_logits`` (the tests) adds ``logits [num_steps // B, S, R, B,
    V]``, every denoise forward's."""
    from distllm_tpu.ops.sampling import (
        fold_row_keys,
        sample_tokens_confidence,
        select_unmask,
    )

    b = cfg.block_length
    steps = b if denoise_steps is None else int(denoise_steps)
    if num_steps % b or not 1 <= steps <= b:
        raise ValueError(
            f'a window of {num_steps} steps is not whole blocks of {b}, or '
            f'{steps} denoise steps are not in [1, {b}]'
        )
    rows = input_ids.shape[0]
    rope = _rope_table(cfg, max_table_positions or cfg.max_position_embeddings)
    first = context_lens // b * b
    given = context_lens - first
    idx = jnp.arange(b, dtype=jnp.int32)
    rep = lambda a: jnp.repeat(a, b)  # noqa: E731 -- a row's value a position
    counts0 = {
        name: jnp.zeros((), jnp.int32)
        for name in ('forwards', 'blocks', 'decided', 'moe_pairs',
                     'moe_pairs_held')
    }

    def one_block(carry, j):
        k_cache, v_cache, counts = carry
        live = steps_left > j * b
        tables = jnp.where(live[:, None], block_tables, 0)
        start = (first + j * b).astype(jnp.int32)
        is_given = (j == 0) & (idx[None, :] < given[:, None])
        ids = jnp.where(is_given, input_ids, cfg.mask_token_id).astype(jnp.int32)
        at = jnp.where(is_given, -1, steps).astype(jnp.int32)

        def denoise(carry, s):
            ids, masked, at, k_cache, v_cache, pairs = carry
            hidden, k_cache, v_cache, step_pairs = _block_pass(
                params, cfg, rope, attn_backend, ids, start, k_cache, v_cache,
                tables, live,
            )
            step_logits = logits(params, cfg, hidden)  # [R, B, V]
            with jax.named_scope('distllm.unmask'):
                counters = (start[:, None] + idx[None, :]) * steps + s
                candidate, confidence = sample_tokens_confidence(
                    step_logits.reshape(rows * b, -1), rep(temperature),
                    rep(top_p), rep(min_p), top_window=sampling_top_window,
                    top_k=rep(top_k),
                    row_keys=fold_row_keys(rep(seeds), counters.reshape(-1)),
                )
                decide = select_unmask(
                    confidence.reshape(rows, b), masked,
                    unmask_schedule(b, steps, s), unmask_threshold,
                )
                ids = jnp.where(decide, candidate.reshape(rows, b), ids)
                at = jnp.where(decide, s, at)
                masked = masked & ~decide
            return (
                (ids, masked, at, k_cache, v_cache, pairs + step_pairs),
                step_logits if return_logits else None,
            )

        (ids, masked, at, k_cache, v_cache, pairs), seen = jax.lax.scan(
            denoise,
            (ids, ~is_given, at, k_cache, v_cache, jnp.zeros((2,), jnp.int32)),
            jnp.arange(steps, dtype=jnp.int32),
        )
        # The committing forward: the same program on the decided block; its
        # K/V are the ones that stay.
        _, k_cache, v_cache, commit_pairs = _block_pass(
            params, cfg, rope, attn_backend, ids, start, k_cache, v_cache,
            tables, live,
        )
        pairs = pairs + commit_pairs
        live_rows = jnp.sum(live, dtype=jnp.int32)
        counts = {
            'forwards': counts['forwards'] + live_rows * (steps + 1),
            'blocks': counts['blocks'] + live_rows,
            'decided': counts['decided'] + jnp.sum(
                (at >= 0) & live[:, None], dtype=jnp.int32
            ),
            'moe_pairs': counts['moe_pairs'] + pairs[0],
            'moe_pairs_held': counts['moe_pairs_held'] + pairs[1],
        }
        return (k_cache, v_cache, counts), (ids, at, seen)

    (k_cache, v_cache, counts), (tokens, at, seen) = jax.lax.scan(
        one_block, (k_cache, v_cache, counts0),
        jnp.arange(num_steps // b, dtype=jnp.int32),
    )
    by_position = lambda a: a.transpose(0, 2, 1).reshape(num_steps, rows)  # noqa: E731
    counters = {**counts, 'decided_at': by_position(at)}
    out = (by_position(tokens), k_cache, v_cache, input_ids, counters)
    return (*out, seen) if return_logits else out
