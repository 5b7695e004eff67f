"""Mistral/Llama/Qwen2-family decoder (SFR-Embedding-Mistral,
Mistral-7B-Instruct; Qwen2 = same architecture + Q/K/V biases).

One implementation serves both reference roles:

- the 7B *embedding* model path (``distllm/embed/encoders/auto.py`` with
  last-token pooling, SURVEY.md section 2.2) via :func:`apply`;
- the *generation* path (vLLM-backed in the reference,
  ``generate/generators/vllm_backend.py``) via :func:`prefill` +
  :func:`decode_step`, which the paged-KV engine drives.

Functional JAX, stacked-layer ``lax.scan``, GQA, RoPE, RMSNorm, SwiGLU; TP
sharding specs over the ``model`` mesh axis (attention heads and MLP width),
matching what the reference delegates to vLLM's ``tensor_parallel_size``.
"""

from __future__ import annotations

import contextlib
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distllm_tpu.models import common
from distllm_tpu.utils import BaseConfig


class MistralConfig(BaseConfig):
    name: Literal['mistral'] = 'mistral'
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None
    intermediate_size: int = 14336
    max_position_embeddings: int = 32768
    rope_theta: float = 10000.0
    # HF rope_scaling dict (Llama-3 'llama3' banding, 'linear') — applied
    # in the RoPE tables; unknown types raise rather than silently
    # mis-position long contexts.
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-5
    sliding_window: int | None = None
    tie_word_embeddings: bool = False
    # Qwen2-family checkpoints (same architecture + Q/K/V projection
    # biases; HF Qwen2Model always has them, MistralModel never does).
    attention_bias: bool = False
    # --- Gemma-family knobs (models/gemma.py sets these; defaults keep
    # every existing family bit-identical). ---
    activation: str = 'silu'  # MLP gate activation (gemma: 'gelu_new')
    embedding_multiplier: float | None = None  # gemma: sqrt(hidden_size)
    norm_plus_one: bool = False  # gemma RMSNorm computes (1 + w)
    post_norms: bool = False  # gemma2 sandwich norms around attn + MLP
    query_scale: float | None = None  # gemma2 query_pre_attn_scalar**-0.5
    attn_logit_softcap: float | None = None  # gemma2 tanh cap on scores
    final_logit_softcap: float | None = None  # gemma2 tanh cap on logits
    # 'all' = every layer uses cfg.sliding_window (Mistral semantics);
    # 'alternating' = gemma2's even-layer-local / odd-layer-global split.
    sliding_window_pattern: Literal['all', 'alternating'] = 'all'
    # Quantized-matmul tier pinned for every dense() in the forward; None
    # reads the process default at trace time. The engine resolves this
    # ONCE at construction (after its TP-mesh compatibility check) so a
    # later process-global change cannot re-route serving dispatches.
    qmm_backend: str | None = None
    dtype: str = 'bfloat16'

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def cache_spec(self) -> common.CacheSpec:
        """One paged group over every layer, held for the whole context: a
        ``sliding_window`` here is a mask over a sequence's blocks, not a
        reason to free them."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_layers),),
            programs=__name__,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'MistralConfig':
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_layers=hf['num_hidden_layers'],
            num_heads=hf['num_attention_heads'],
            num_kv_heads=hf.get('num_key_value_heads', hf['num_attention_heads']),
            head_dim=hf.get('head_dim'),
            intermediate_size=hf['intermediate_size'],
            max_position_embeddings=hf.get('max_position_embeddings', 32768),
            rope_theta=hf.get('rope_theta', 10000.0),
            rope_scaling=hf.get('rope_scaling'),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-5),
            # Qwen2 config.json carries sliding_window even when
            # use_sliding_window is false — honor the switch (Mistral
            # configs have no switch; absent means enabled-if-set).
            sliding_window=(
                hf.get('sliding_window')
                if hf.get('use_sliding_window', True)
                else None
            ),
            tie_word_embeddings=hf.get('tie_word_embeddings', False),
            attention_bias=hf.get(
                'attention_bias', hf.get('model_type') == 'qwen2'
            ),
        )


def init(rng: jax.Array, cfg: MistralConfig) -> dict:
    h = cfg.hidden_size
    hd = cfg.head_size
    q_out = cfg.num_heads * hd
    kv_out = cfg.num_kv_heads * hd
    i = cfg.intermediate_size
    scale = 0.02

    def normal(key, shape):
        return np.asarray(jax.random.normal(key, shape) * scale, np.float32)

    keys = jax.random.split(rng, 3)
    layers = []
    for li in range(cfg.num_layers):
        ks = jax.random.split(jax.random.fold_in(keys[0], li), 10)

        def proj(kkey, bkey, shape):
            out = {'kernel': normal(kkey, shape)}
            if cfg.attention_bias:
                out['bias'] = normal(bkey, (shape[-1],))
            return out

        # Gemma's (1+w) norms are identity at w=0; others at w=1.
        ln_init = 0.0 if cfg.norm_plus_one else 1.0
        lp = {
            'q': proj(ks[0], ks[7], (h, q_out)),
            'k': proj(ks[1], ks[8], (h, kv_out)),
            'v': proj(ks[2], ks[9], (h, kv_out)),
            'o': {'kernel': normal(ks[3], (q_out, h))},
            'attn_ln': {'scale': np.full((h,), ln_init, np.float32)},
            'gate': {'kernel': normal(ks[4], (h, i))},
            'up': {'kernel': normal(ks[5], (h, i))},
            'down': {'kernel': normal(ks[6], (i, h))},
            'mlp_ln': {'scale': np.full((h,), ln_init, np.float32)},
        }
        if cfg.post_norms:
            lp['post_attn_ln'] = {'scale': np.full((h,), ln_init, np.float32)}
            lp['post_mlp_ln'] = {'scale': np.full((h,), ln_init, np.float32)}
        layers.append(lp)
    params = {
        'embed': normal(keys[1], (cfg.vocab_size, h)),
        'layers': common.stack_layers(layers),
        'final_ln': {
            'scale': np.full(
                (h,), 0.0 if cfg.norm_plus_one else 1.0, np.float32
            )
        },
    }
    if not cfg.tie_word_embeddings:
        params['lm_head'] = normal(keys[2], (h, cfg.vocab_size))
    return params


def init_on_device(rng: jax.Array, cfg: MistralConfig) -> dict:
    """Random params generated directly on device in ``cfg.dtype``.

    ``init`` materialises fp32 numpy on host (fine for test-sized models,
    and the fp32 master copy is what ``params_from_hf`` produces too); at
    7B dims that is 29 GB and cannot live in a 16 GB chip's HBM.  Serving
    only ever reads the weights in ``cfg.dtype``, so for benchmarks we
    generate the stacked layer tree straight on device in that dtype —
    one RNG call per parameter *kind* (leading L axis), never per layer.
    """
    h = cfg.hidden_size
    hd = cfg.head_size
    q_out = cfg.num_heads * hd
    kv_out = cfg.num_kv_heads * hd
    i = cfg.intermediate_size
    L = cfg.num_layers
    dtype = jnp.dtype(cfg.dtype)
    scale = 0.02

    keys = jax.random.split(rng, 12)

    @jax.jit
    def build():
        def normal(key, shape):
            return jax.random.normal(key, shape, dtype=jnp.float32).astype(
                dtype
            ) * scale

        def proj(kkey, bkey, shape):
            out = {'kernel': normal(kkey, shape)}
            if cfg.attention_bias:
                out['bias'] = normal(bkey, (L, shape[-1]))
            return out

        ln_init = 0.0 if cfg.norm_plus_one else 1.0
        params = {
            'embed': normal(keys[0], (cfg.vocab_size, h)),
            'layers': {
                'q': proj(keys[1], keys[9], (L, h, q_out)),
                'k': proj(keys[2], keys[10], (L, h, kv_out)),
                'v': proj(keys[3], keys[11], (L, h, kv_out)),
                'o': {'kernel': normal(keys[4], (L, q_out, h))},
                'attn_ln': {'scale': jnp.full((L, h), ln_init, dtype)},
                'gate': {'kernel': normal(keys[5], (L, h, i))},
                'up': {'kernel': normal(keys[6], (L, h, i))},
                'down': {'kernel': normal(keys[7], (L, i, h))},
                'mlp_ln': {'scale': jnp.full((L, h), ln_init, dtype)},
            },
            'final_ln': {'scale': jnp.full((h,), ln_init, dtype)},
        }
        if cfg.post_norms:
            params['layers']['post_attn_ln'] = {
                'scale': jnp.full((L, h), ln_init, dtype)
            }
            params['layers']['post_mlp_ln'] = {
                'scale': jnp.full((L, h), ln_init, dtype)
            }
        if not cfg.tie_word_embeddings:
            params['lm_head'] = normal(keys[8], (h, cfg.vocab_size))
        return params

    return build()


def _mlp_block(normed: jnp.ndarray, lp: dict, cfg) -> jnp.ndarray:
    """Per-layer MLP: dense SwiGLU, or the Mixtral MoE bank when the layer
    carries a router (pytree STRUCTURE is static under jit, so this
    branch costs nothing at trace time). One home for the block lets the
    whole serving machinery, prefill and paged decode alike,
    serve both families (the reference's vLLM serves Mistral and Mixtral
    through one engine too)."""
    if 'router' in lp:
        from distllm_tpu.models.mixtral import moe_mlp

        batched = normed[:, None] if normed.ndim == 2 else normed
        out = moe_mlp(
            batched,
            lp['router']['kernel'],
            lp['gate']['kernel'],
            lp['up']['kernel'],
            lp['down']['kernel'],
            # Router present => the config is MoE; a missing field must
            # raise, not silently route top-2.
            cfg.experts_per_token,
        )
        return out[:, 0] if normed.ndim == 2 else out
    act = common.ACTIVATIONS[getattr(cfg, 'activation', 'silu')]
    qb = getattr(cfg, 'qmm_backend', None)
    return common.dense(
        act(common.dense(normed, lp['gate']['kernel'], qmm_backend=qb))
        * common.dense(normed, lp['up']['kernel'], qmm_backend=qb),
        lp['down']['kernel'],
        qmm_backend=qb,
    )


def _rope_tables(cfg: MistralConfig, max_len: int):
    cos, sin = common.rope_frequencies(
        cfg.head_size, max_len, cfg.rope_theta,
        getattr(cfg, 'rope_scaling', None),
    )
    return jnp.asarray(cos), jnp.asarray(sin)


def _norm(x: jnp.ndarray, scale: jnp.ndarray, cfg) -> jnp.ndarray:
    return common.rms_norm(
        x, scale, cfg.rms_norm_eps,
        plus_one=getattr(cfg, 'norm_plus_one', False),
    )


def _embed_tokens(params: dict, cfg, input_ids: jnp.ndarray) -> jnp.ndarray:
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.asarray(params['embed'])[input_ids].astype(dtype)
    if getattr(cfg, 'embedding_multiplier', None) is not None:
        # Gemma scales embeddings by sqrt(hidden) CAST TO THE COMPUTE
        # DTYPE (HF casts the normalizer tensor); matching the rounding
        # keeps bf16 goldens exact.
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype)
    return x


def _layer_window_flags(cfg) -> jnp.ndarray:
    """Per-layer bool [L]: does layer i use the sliding window?
    (gemma2 'alternating': even layers local, odd layers global)."""
    return jnp.arange(cfg.num_layers) % 2 == 0


def _attn_mask(attention_mask: jnp.ndarray, cfg: MistralConfig) -> jnp.ndarray:
    """Causal x key-validity boolean mask ``[B, 1, S, S]`` (+ sliding window)."""
    seq = attention_mask.shape[1]
    causal = common.causal_mask(seq, seq)
    if cfg.sliding_window is not None:
        q_pos = jnp.arange(seq)[:, None]
        kv_pos = jnp.arange(seq)[None, :]
        causal = causal & (kv_pos > q_pos - cfg.sliding_window)
    return causal[None, None] & attention_mask[:, None, None, :].astype(bool)


def _unscoped(name: str):
    """No scope: this family's programs carry no scope names. A family that
    wants the halves of a layer found by name passes ``jax.named_scope``."""
    return contextlib.nullcontext()


def _mlp_half(x: jnp.ndarray, lp: dict, cfg) -> jnp.ndarray:
    """A layer's second half: the MLP behind its norm (and before its own,
    under sandwich norms) onto the residual."""
    normed2 = _norm(x, lp['mlp_ln']['scale'], cfg)
    mlp = _mlp_block(normed2, lp, cfg)
    if getattr(cfg, 'post_norms', False):
        mlp = _norm(mlp, lp['post_mlp_ln']['scale'], cfg)
    return x + mlp


def _span_layer(  # distlint: traced
    cfg, rope, attn_backend, span, carry, lp, plane, window_l,
    scope=_unscoped,
):
    """One layer over one span of every row through the paged cache:
    ``prefill_paged``'s scan body. ``lp`` is the layer's weights and
    ``plane`` the layer of the stacked pools it writes and reads: apart,
    because a stack that runs several times (``models/ouro.py``) gives every
    pass planes of its own. ``span`` is ``(positions, valid, block_tables,
    context_lens, tail_lens)``, ``carry`` and the result ``(x, k_cache,
    v_cache)``."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention,
        write_chunk_kv,
    )

    (cos, sin), (x, k_cache, v_cache) = rope, carry
    positions, valid, block_tables, context_lens, tail_lens = span
    alternating = (
        getattr(cfg, 'sliding_window_pattern', 'all') == 'alternating'
    )
    qb = getattr(cfg, 'qmm_backend', None)
    with scope('distllm.attn_full'):
        normed = _norm(x, lp['attn_ln']['scale'], cfg)
        q = common.split_heads(
            common.dense(
                normed, lp['q']['kernel'], lp['q'].get('bias'), qmm_backend=qb
            ),
            cfg.num_heads,
        )
        k = common.split_heads(
            common.dense(
                normed, lp['k']['kernel'], lp['k'].get('bias'), qmm_backend=qb
            ),
            cfg.num_kv_heads,
        )
        v = common.split_heads(
            common.dense(
                normed, lp['v']['kernel'], lp['v'].get('bias'), qmm_backend=qb
            ),
            cfg.num_kv_heads,
        )
        q = common.apply_rope(q, cos, sin, positions)
        k = common.apply_rope(k, cos, sin, positions)
        # Write the tail's K/V first, then attend over the paged cache —
        # cached prefix and own chunk through one gather (decode's
        # write-then-attend order, generalized to S queries). The stacked
        # pools go to both whole, with the layer whose pages are meant: a
        # layer sliced out would be copied out of the pool and back.
        k_cache, v_cache = write_chunk_kv(
            k_cache, v_cache, k, v, block_tables, positions, valid,
            layer=plane,
        )
        # q_lens masks PADDING queries (XLA: onto key 0; Pallas: to exact
        # zeros): under a sliding window a pad query past the window's
        # reach otherwise has an all-masked score row -> NaN attention ->
        # NaN K/V written to the TRASH block -> every later dispatch
        # whose block-table padding gathers block 0 poisons its softmax·V
        # contraction (0 x NaN = NaN). Valid rows are bit-identical with
        # or without the mask.
        attn = ragged_paged_attention(
            q, k_cache, v_cache, block_tables, context_lens, positions,
            q_lens=tail_lens,
            sliding_window=(
                window_l if alternating else cfg.sliding_window
            ),
            scale=getattr(cfg, 'query_scale', None),
            logit_softcap=getattr(cfg, 'attn_logit_softcap', None),
            backend=attn_backend, layer=plane,
        )
        attn_out = common.dense(
            common.merge_heads(attn), lp['o']['kernel'], qmm_backend=qb
        )
        if getattr(cfg, 'post_norms', False):
            attn_out = _norm(attn_out, lp['post_attn_ln']['scale'], cfg)
        x = x + attn_out
    with scope('distllm.dense_mlp'):
        x = _mlp_half(x, lp, cfg)
    return x, k_cache, v_cache


def _token_layer(  # distlint: traced
    cfg, rope, attn_backend, row, carry, lp, plane, window_l,
    scope=_unscoped,
):
    """One layer over one token of every row: ``_decode_core``'s scan body,
    with the layer's weights ``lp`` and its ``plane`` of the stacked pools
    apart, as :func:`_span_layer` has them. ``row`` is ``(positions,
    block_tables, context_lens)``, ``carry`` and the result ``(x, k_cache,
    v_cache)``."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    (cos, sin), (x, k_cache, v_cache) = rope, carry
    positions, block_tables, context_lens = row
    alternating = (
        getattr(cfg, 'sliding_window_pattern', 'all') == 'alternating'
    )
    qb = getattr(cfg, 'qmm_backend', None)
    with scope('distllm.attn_full'):
        normed = _norm(x, lp['attn_ln']['scale'], cfg)
        q = common.dense(
            normed, lp['q']['kernel'], lp['q'].get('bias'), qmm_backend=qb
        ).reshape(-1, cfg.num_heads, cfg.head_size)
        k = common.dense(
            normed, lp['k']['kernel'], lp['k'].get('bias'), qmm_backend=qb
        ).reshape(-1, cfg.num_kv_heads, cfg.head_size)
        v = common.dense(
            normed, lp['v']['kernel'], lp['v'].get('bias'), qmm_backend=qb
        ).reshape(-1, cfg.num_kv_heads, cfg.head_size)
        # RoPE at each sequence's own position ([B, 1, N, Hd] view).
        q = common.apply_rope(q[:, None], cos, sin, positions[:, None])[:, 0]
        k = common.apply_rope(k[:, None], cos, sin, positions[:, None])[:, 0]
        k_cache, v_cache = write_token_kv(
            k_cache, v_cache, k, v, block_tables, positions, layer=plane
        )
        attn = decode_attention(
            q, k_cache, v_cache, block_tables, context_lens, positions,
            backend=attn_backend, layer=plane,
            # Traced per-layer window only for the alternating pattern;
            # other families keep the static value so their decode HLO
            # is unchanged.
            sliding_window=window_l if alternating else cfg.sliding_window,
            scale=getattr(cfg, 'query_scale', None),
            logit_softcap=getattr(cfg, 'attn_logit_softcap', None),
        )
        attn_out = common.dense(
            attn.reshape(-1, cfg.num_heads * cfg.head_size),
            lp['o']['kernel'],
            qmm_backend=qb,
        )
        if getattr(cfg, 'post_norms', False):
            attn_out = _norm(attn_out, lp['post_attn_ln']['scale'], cfg)
        x = x + attn_out
    with scope('distllm.dense_mlp'):
        x = _mlp_half(x, lp, cfg)
    return x, k_cache, v_cache


def apply(  # distlint: traced
    params: dict,
    cfg: MistralConfig,
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray,
    *,
    mesh=None,
    seq_parallel: str | None = None,
) -> jnp.ndarray:
    """Dense causal forward: ``[B, S]`` → last hidden states ``[B, S, H]``.

    ``seq_parallel`` (``'ring'`` or ``'ulysses'``) activates sequence/context
    parallelism over ``mesh``'s ``seq`` axis: activations stay sharded
    ``S/P`` per chip and attention runs as ring ppermutes / all-to-alls
    (``distllm_tpu.ops.ring_attention``) — the long-context capability the
    reference lacks entirely (it truncates, ``auto.py:74``; SURVEY.md §5).
    """
    hidden, _, _ = _forward(
        params, cfg, input_ids, attention_mask, collect_kv=False,
        mesh=mesh, seq_parallel=seq_parallel,
    )
    return hidden


def prefill(  # distlint: traced
    params: dict,
    cfg: MistralConfig,
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Forward that also returns per-layer K/V ``[L, B, S, N_kv, Hd]``."""
    return _forward(params, cfg, input_ids, attention_mask, collect_kv=True)


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: MistralConfig,
    input_ids: jnp.ndarray,  # [B, S] uncached tail tokens (padded)
    positions: jnp.ndarray,  # [B, S] absolute position of each tail token
    k_cache: jnp.ndarray,  # [L, num_blocks, block_size, N_kv * Hd]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] total valid tokens incl. this tail
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    max_table_positions: int | None = None,
    all_logits: bool = False,
    attn_backend: str = 'xla',
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill an UNCACHED TAIL against KV history already in the paged
    cache — the prefix-cache hit / chunked-prefill forward
    (docs/prefix_caching.md).

    Unlike :func:`prefill` (whole prompt, K/V returned for one batched
    scatter afterwards), the caches ride the layer scan: each layer writes
    its tail K/V into its cache plane FIRST, then the tail queries attend
    over the paged cache — cached prefix and own chunk together — via
    :func:`~distllm_tpu.ops.paged_attention.ragged_paged_attention`
    (``q_lens=tail_lens`` — the rows are ragged per-row query spans;
    ``attn_backend`` selects the XLA baseline or the fused Pallas kernel,
    resolved once by the engine at construction). Returns
    ``(last_logits [B, V] fp32, k_cache, v_cache)`` where ``last_logits``
    is sampled at each row's last valid tail position. Positions at or
    past ``tail_lens`` (padding) write to trash block 0 and their logits
    are garbage the caller discards.

    ``all_logits=True`` (speculative verification, :func:`spec_window`)
    returns logits at EVERY span position — ``[B, S, V]`` — instead of
    only the last one; the forward pass itself is unchanged, so the
    verify dispatch shares every numeric property of this path (the
    greedy-identity backbone of docs/speculative.md).
    """
    b, s = input_ids.shape
    table_len = max_table_positions or cfg.max_position_embeddings
    rope = _rope_tables(cfg, table_len)
    layer_windows = jnp.where(
        _layer_window_flags(cfg), cfg.sliding_window or 0, 0
    ).astype(jnp.int32)
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]  # [B, S]
    x = _embed_tokens(params, cfg, input_ids)  # [B, S, H]
    span = (positions, valid, block_tables, context_lens, tail_lens)

    def layer(carry, xs):
        lp, li, window_l = xs
        # the layer's weights and its plane of the pools are one index here
        return _span_layer(
            cfg, rope, attn_backend, span, carry, lp, li, window_l
        ), None

    (x, k_cache, v_cache), _ = jax.lax.scan(
        layer,
        (x, k_cache, v_cache),
        (
            params['layers'],
            jnp.arange(cfg.num_layers, dtype=jnp.int32),
            layer_windows,
        ),
    )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    if all_logits:
        # Speculative verification needs every span position's logits;
        # spans are short (1 + draft_k), so [B, S, V] stays small.
        return logits(params, cfg, hidden), k_cache, v_cache
    # Only each row's last valid tail position feeds the lm_head ([B, S, V]
    # logits would waste MXU time and HBM — same policy as prefill).
    last_hidden = common.last_token(hidden, tail_lens)
    return logits(params, cfg, last_hidden)[:, 0], k_cache, v_cache


def _forward(
    params, cfg, input_ids, attention_mask, *, collect_kv,
    mesh=None, seq_parallel=None,
):
    rope = _rope_tables(cfg, input_ids.shape[1])
    x = _embed_tokens(params, cfg, input_ids)
    x, kv = _dense_layers(
        params, cfg, rope, x, attention_mask, collect_kv=collect_kv,
        mesh=mesh, seq_parallel=seq_parallel,
    )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    if collect_kv:
        return hidden, kv[0], kv[1]
    return hidden, None, None


def _dense_layers(  # distlint: traced
    params, cfg, rope, x, attention_mask, *, collect_kv,
    mesh=None, seq_parallel=None,
):
    """The stack once over ``x [B, S, H]`` with no cache: ``_forward``
    between the embedding and the final norm (a stack that runs several
    times calls it a pass). Returns ``(x, (k, v) or None)``."""
    cos, sin = rope
    use_sp = (
        seq_parallel is not None
        and mesh is not None
        and mesh.shape.get('seq', 1) > 1
    )
    if use_sp and cfg.sliding_window is not None:
        raise NotImplementedError(
            'sequence parallelism with sliding-window attention'
        )
    if use_sp and getattr(cfg, 'attn_logit_softcap', None) is not None:
        raise NotImplementedError(
            'sequence parallelism with attention logit softcapping'
        )
    alternating = (
        getattr(cfg, 'sliding_window_pattern', 'all') == 'alternating'
    )
    if alternating and not use_sp:
        # Per-layer mask choice (gemma2): global causal for odd layers,
        # windowed for even — both built once, selected per scan step.
        full_mask = _attn_mask(
            attention_mask, cfg.model_copy(update={'sliding_window': None})
        )
        win_mask = _attn_mask(attention_mask, cfg)
        mask = full_mask
    else:
        mask = None if use_sp else _attn_mask(attention_mask, cfg)
    positions = None  # prefill positions are 0..S-1 per row

    def layer(x, xs):
        lp, win_flag = xs
        if alternating and not use_sp:
            mask_l = jnp.where(win_flag, win_mask, full_mask)
        else:
            mask_l = mask
        normed = _norm(x, lp['attn_ln']['scale'], cfg)
        qb = getattr(cfg, 'qmm_backend', None)
        q = common.split_heads(
            common.dense(
                normed, lp['q']['kernel'], lp['q'].get('bias'), qmm_backend=qb
            ),
            cfg.num_heads,
        )
        k = common.split_heads(
            common.dense(
                normed, lp['k']['kernel'], lp['k'].get('bias'), qmm_backend=qb
            ),
            cfg.num_kv_heads,
        )
        v = common.split_heads(
            common.dense(
                normed, lp['v']['kernel'], lp['v'].get('bias'), qmm_backend=qb
            ),
            cfg.num_kv_heads,
        )
        q = common.apply_rope(q, cos, sin, positions)
        k = common.apply_rope(k, cos, sin, positions)
        if use_sp:
            from distllm_tpu.ops.ring_attention import (
                ring_attention,
                ulysses_attention,
            )

            sp_fn = ring_attention if seq_parallel == 'ring' else ulysses_attention
            n_rep = cfg.num_heads // cfg.num_kv_heads
            attn = sp_fn(
                q,
                common.repeat_kv(k, n_rep),
                common.repeat_kv(v, n_rep),
                mesh,
                kv_mask=attention_mask,
                causal=True,
            )
        else:
            # GQA handled natively by the fused attention (no KV
            # materialization).
            attn = common.sdpa(
                q, k, v, mask=mask_l,
                scale=getattr(cfg, 'query_scale', None),
                logit_softcap=getattr(cfg, 'attn_logit_softcap', None),
            )
        attn_out = common.dense(
            common.merge_heads(attn), lp['o']['kernel'], qmm_backend=qb
        )
        if getattr(cfg, 'post_norms', False):
            attn_out = _norm(attn_out, lp['post_attn_ln']['scale'], cfg)
        x = _mlp_half(x + attn_out, lp, cfg)
        return x, (k, v) if collect_kv else None

    return jax.lax.scan(
        layer, x, (params['layers'], _layer_window_flags(cfg))
    )


def _decode_core(
    params: dict,
    cfg: MistralConfig,
    rope: tuple,  # (cos, sin)
    attn_backend: str,
    input_ids: jnp.ndarray,  # [B]
    positions: jnp.ndarray,  # [B]
    context_lens: jnp.ndarray,  # [B]
    caches: tuple,  # (k_cache, v_cache)
    block_tables: jnp.ndarray,  # [B, max_blocks]
    live=None,  # nothing here keeps a row's state or counts
) -> tuple[jnp.ndarray, tuple, tuple]:
    """One decode step's compute, RoPE tables passed in (so a multi-step
    scan hoists them out of the loop): ``common.decode_window``'s ``core``
    once its first four arguments are bound. Returns ``(logits, (k_cache,
    v_cache), ())``.

    The layer scan is UNROLLED. Decode is weight-bandwidth bound, and a
    rolled scan's per-iteration dynamic-slice of the stacked MLP kernels
    was materialized by XLA as a temp a layer (read off the compiled HLO on
    older code, 2026-07-31; not re-measured: no cell runs a rolled window).
    Unrolled, those are static slices that fold into the matmuls. The K/V
    pools are never sliced: a layer sliced out of the stacked pool for the
    kernel call was copied out and back (64 plane copies and write-backs a
    step at 32 layers, 4.27 ms of a 29.61 ms step on the chip, PR 31), so the pool
    goes to the writer and to the kernel whole, with the layer whose
    pages are meant (``ops.paged_attention._layer_pages``): the form of
    every family's K/V pool (``models.common.CacheSpec``).
    Prefill keeps the rolled scan: compute-bound,
    and the weights' slice traffic amortizes over the whole token batch.
    """
    # int32 [L] per-layer windows (0 = global) riding the layer scan; only
    # consulted under the alternating pattern.
    layer_windows = jnp.where(
        _layer_window_flags(cfg), cfg.sliding_window or 0, 0
    ).astype(jnp.int32)

    k_cache, v_cache = caches
    x = _embed_tokens(params, cfg, input_ids)  # [B, H]
    row = (positions, block_tables, context_lens)

    # The FULL caches ride the scan carry and each layer scatters its new
    # rows into its own pages of them, in place. Unrolled, that chain of
    # scatters sits in straight-line code, where in-place updates rely on
    # XLA's buffer reuse instead of a while loop's carry aliasing:
    # tests/test_aot_tpu.py asserts that no op of the window has a plane as
    # its result and every pool-sized one is the scatter, so a missed reuse
    # cannot land silently. (Scanning the caches as xs/ys instead
    # allocates a full stacked output buffer: +1 GB at 7B dims, and one
    # more when a multi-step window scan wraps this — that overflowed the
    # v5e's 16 GB HBM.)
    def layer(carry, xs):
        lp, li, window_l = xs
        return _token_layer(
            cfg, rope, attn_backend, row, carry, lp, li, window_l
        ), None

    (x, k_cache, v_cache), _ = jax.lax.scan(
        layer,
        (x, k_cache, v_cache),
        (
            params['layers'],
            jnp.arange(cfg.num_layers, dtype=jnp.int32),
            layer_windows,
        ),
        unroll=cfg.num_layers,
    )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    return logits(params, cfg, hidden), (k_cache, v_cache), ()


def decode_step(  # distlint: traced
    params: dict,
    cfg: MistralConfig,
    input_ids: jnp.ndarray,  # [B] one new token per sequence
    positions: jnp.ndarray,  # [B] 0-based index of that token
    k_cache: jnp.ndarray,  # [L, num_blocks, block_size, N_kv * Hd]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] valid tokens incl. the new one
    attn_backend: str = 'xla',
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-token decode over the paged KV cache.

    Returns ``(logits [B, V] fp32, k_cache, v_cache)`` with the new token's
    K/V written into the paged blocks. Inactive batch slots should point
    their block table rows at the reserved trash block 0.

    ``attn_backend`` selects the XLA gather baseline or the fused ragged
    Pallas kernel (span-1 degenerate case; 'interpret' runs the same
    kernel on the Pallas interpreter). All backends support sliding
    windows, gemma2 alternating layers, softcap, and custom scales.
    """
    rope = _rope_tables(cfg, cfg.max_position_embeddings)
    logits_, caches, _ = _decode_core(
        params, cfg, rope, attn_backend, input_ids, positions, context_lens,
        (k_cache, v_cache), block_tables,
    )
    return logits_, *caches


def decode_loop(  # distlint: traced
    params: dict,
    cfg: MistralConfig,
    input_ids: jnp.ndarray,  # [B] last emitted token per slot
    positions: jnp.ndarray,  # [B] 0-based index of that token
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] — covers +num_steps tokens
    context_lens: jnp.ndarray,  # [B] valid tokens incl. the input token
    steps_left: jnp.ndarray,  # [B] int32 — tokens this slot may emit now
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    min_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 disables)
    seeds: jnp.ndarray,  # [B] uint32 per-request sampling seeds
    num_steps: int,
    attn_backend: str = 'xla',
    max_table_positions: int | None = None,
    sampling_top_window: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``num_steps`` fused decode+sample steps in ONE dispatch.

    The TPU-first answer to the reference's per-token GPU decode loop
    (vLLM inside ``generate/generators/vllm_backend.py``): a per-token
    loop syncs host and device once per token, so the engine generates a
    *window* of tokens per dispatch — each step's sampled token feeds the next step's input
    entirely on device, and only the ``[num_steps, B]`` token block travels
    to host (asynchronously, once per window).

    Per-slot ``steps_left`` masks slots that run out of budget mid-window
    (max_tokens / max_model_len): their KV writes are routed to the
    reserved trash block 0 and their later tokens are garbage the host
    discards. The scheduler must have reserved blocks for ``min(num_steps,
    steps_left)`` extra tokens per slot. The step scan, with its sampling
    keys, is every family's: ``common.decode_window``.

    Returns ``(tokens [num_steps, B] int32, k_cache, v_cache, last_ids)``.
    """
    from functools import partial

    # RoPE tables bounded by what positions can actually reach: the block
    # table row covers max_table_positions tokens (engine max_model_len) —
    # far smaller than the checkpoint's 32k max_position_embeddings.
    rope = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    tokens, (k_cache, v_cache), ids, _ = common.decode_window(
        partial(_decode_core, params, cfg, rope, attn_backend),
        input_ids, positions, context_lens, (k_cache, v_cache),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=(),
    )
    return tokens, k_cache, v_cache, ids


def mixed_window(  # distlint: traced
    params: dict,
    cfg: MistralConfig,
    # --- decode operands (identical to decode_loop) ---
    input_ids: jnp.ndarray,  # [B] last emitted token per slot
    positions: jnp.ndarray,  # [B]
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B]
    steps_left: jnp.ndarray,  # [B] int32
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    min_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 disables)
    seeds: jnp.ndarray,  # [B] uint32 per-request sampling seeds
    # --- ragged prefill-chunk operands (prefill_paged shapes) ---
    chunk_ids: jnp.ndarray,  # [C, S] uncached tail-span tokens (padded)
    chunk_positions: jnp.ndarray,  # [C, S] absolute positions
    chunk_block_tables: jnp.ndarray,  # [C, max_blocks]
    chunk_context_lens: jnp.ndarray,  # [C] valid tokens incl. the span
    chunk_tail_lens: jnp.ndarray,  # [C] valid tokens in chunk_ids (0 = pad)
    chunk_temperature: jnp.ndarray,  # [C]
    chunk_top_p: jnp.ndarray,  # [C]
    chunk_min_p: jnp.ndarray,  # [C]
    chunk_top_k: jnp.ndarray,  # [C] int32 (0 disables)
    chunk_seeds: jnp.ndarray,  # [C] uint32 per-request sampling seeds
    num_steps: int,
    attn_backend: str = 'xla',
    max_table_positions: int | None = None,
    sampling_top_window: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One MIXED serving window: ragged prefill-chunk rows + the fused
    decode scan in a single dispatch (docs/serving.md).

    The decode window streams every weight regardless of how many tokens
    ride it, and each standalone prefill dispatch between windows
    serializes against the decode pipeline (what that costs on the chip
    is not measured on today's code). Folding the uncached prefill-tail
    chunks into the window dispatch removes those dispatches: the chunk
    rows' write-then-attend pass
    (:func:`prefill_paged`, ragged per-row ``chunk_tail_lens`` — decode-
    like rows of span 1 coexist with causal multi-token chunk rows) runs
    first, then the unchanged decode scan. Chunk rows and decode rows own
    disjoint KV blocks, so the fusion is value-exact: both halves compute
    bit-identically to their standalone dispatches.

    Returns ``(tokens [num_steps, B], k_cache, v_cache, last_ids,
    chunk_tokens [C])`` where ``chunk_tokens`` samples each chunk row's
    last valid position (meaningful only for rows that finish their tail
    this window; the engine discards the rest). Every draw — chunk and
    decode alike — uses the counter-derived per-row key for the token
    being produced (``fold_row_keys``), so stochastic tokens are identical
    to the pure separate-prefill path too, not just greedy ones.
    """
    from distllm_tpu.ops.sampling import fold_row_keys, sample_tokens

    chunk_logits, k_cache, v_cache = prefill_paged(
        params, cfg, chunk_ids, chunk_positions, k_cache, v_cache,
        chunk_block_tables, chunk_context_lens, chunk_tail_lens,
        max_table_positions=max_table_positions, attn_backend=attn_backend,
    )
    # A chunk row's sampled token is its prompt's first generated token:
    # absolute index == chunk_context_lens (tokens 0..ctx-1 are prompt).
    chunk_tokens = sample_tokens(
        chunk_logits, None, chunk_temperature, chunk_top_p,
        chunk_min_p, top_window=sampling_top_window, top_k=chunk_top_k,
        row_keys=fold_row_keys(chunk_seeds, chunk_context_lens),
    )
    tokens, k_cache, v_cache, last_ids = decode_loop(
        params, cfg, input_ids, positions, k_cache, v_cache, block_tables,
        context_lens, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, attn_backend=attn_backend,
        max_table_positions=max_table_positions,
        sampling_top_window=sampling_top_window,
    )
    return tokens, k_cache, v_cache, last_ids, chunk_tokens


def spec_window(  # distlint: traced
    params: dict,
    cfg: MistralConfig,
    # --- ragged verify-span operands (prefill_paged shapes) ---
    span_ids: jnp.ndarray,  # [B, S] last emitted token + draft tokens
    span_positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] total valid tokens incl. the span
    span_lens: jnp.ndarray,  # [B] valid span tokens (0 = inactive slot)
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    min_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 disables)
    seeds: jnp.ndarray,  # [B] uint32 per-request sampling seeds
    # --- optional prefill-chunk operands (mixed batching composition) ---
    chunk: tuple | None = None,  # (ids, pos, bt, ctx, tails, temp, tp,
    #                               mp, tk, seeds)
    max_table_positions: int | None = None,
    sampling_top_window: int = 0,
    attn_backend: str = 'xla',
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray | None]:
    """One SPECULATIVE verify window: score every row's draft span and run
    the accept/resample rule in a single ragged dispatch
    (docs/speculative.md "Sampled verification").

    Each row carries ``[last_emitted_token, d_1, .., d_k]`` at absolute
    positions ``num_tokens-1 ..`` — the exact per-row-query-span shape
    :func:`prefill_paged` already dispatches (write-then-attend through
    ``ragged_paged_attention_xla``), so one weight pass scores all
    ``1+draft_k`` positions. Verification happens device-side in
    :func:`distllm_tpu.ops.sampling.verify_spans`: greedy rows keep the
    longest prefix where draft ``d_{i+1}`` equals the argmax at position
    ``i`` (bit-identical to the pre-sampled-verification host loop);
    temperature > 0 rows run exact rejection sampling against the filtered
    target (accept w.p. min(1, p̃/q); resample the positive residual on
    the first rejection). Acceptance decisions never bounce through the
    host mid-dispatch — only the packed tokens + accept length travel back
    at the engine's one audited fetch point. Rejected suffixes need no
    device-side rollback: their K/V writes sit at positions at or beyond
    the row's post-acceptance ``num_tokens``, which every later dispatch
    either overwrites before attending (write-then-attend) or masks out
    (``kv_pos <= q_pos``).

    ``chunk`` (pytree-static; ``None`` compiles a chunk-free graph)
    carries mixed-batching prefill-chunk rows exactly as
    :func:`mixed_window` does — same :func:`prefill_paged` pass, so the
    chunk half stays bit-identical to its standalone dispatch.

    Returns ``(packed [B, S+1] int32, k_cache, v_cache, chunk_tokens
    [C] | None)`` where ``packed[:, :S]`` are the per-position output
    tokens and ``packed[:, S]`` is the accepted-draft count (see
    :func:`verify_spans`). All draws use counter-derived per-row keys, so
    a span-1 verify of a sampled row emits the exact token the decode
    scan would have.
    """
    from distllm_tpu.ops.sampling import (
        fold_row_keys,
        sample_tokens,
        verify_spans,
    )

    chunk_tokens = None
    if chunk is not None:
        (c_ids, c_pos, c_bt, c_ctx, c_tails, c_temp, c_top_p, c_min_p,
         c_top_k, c_seeds) = chunk
        chunk_logits, k_cache, v_cache = prefill_paged(
            params, cfg, c_ids, c_pos, k_cache, v_cache, c_bt, c_ctx,
            c_tails, max_table_positions=max_table_positions,
            attn_backend=attn_backend,
        )
        chunk_tokens = sample_tokens(
            chunk_logits, None, c_temp, c_top_p, c_min_p,
            top_window=sampling_top_window, top_k=c_top_k,
            row_keys=fold_row_keys(c_seeds, c_ctx),
        )
    span_logits, k_cache, v_cache = prefill_paged(
        params, cfg, span_ids, span_positions, k_cache, v_cache,
        block_tables, context_lens, span_lens,
        max_table_positions=max_table_positions, all_logits=True,
        attn_backend=attn_backend,
    )
    packed = verify_spans(
        span_logits, span_ids, span_lens, span_positions,
        temperature, top_p, min_p, top_k, seeds,
        top_window=sampling_top_window,
    )
    return packed, k_cache, v_cache, chunk_tokens


def logits(params: dict, cfg: MistralConfig, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """LM head: ``[..., H]`` hidden → fp32 ``[..., V]`` logits."""
    if cfg.tie_word_embeddings or 'lm_head' not in params:
        kernel = jnp.asarray(params['embed']).T
    else:
        kernel = jnp.asarray(params['lm_head'])
    out = common.dense(
        hidden, kernel, qmm_backend=getattr(cfg, 'qmm_backend', None)
    ).astype(jnp.float32)
    if getattr(cfg, 'final_logit_softcap', None) is not None:
        out = common.softcap(out, cfg.final_logit_softcap)
    return out


def param_specs(cfg: MistralConfig, params: dict | None = None) -> dict:
    """Sharding specs structurally matching ``params``.

    Encoder-only checkpoints (SFR-Embedding-Mistral) have no ``lm_head`` even
    with untied embeddings, so the spec tree mirrors the actual params when
    they are provided.
    """
    col = {'kernel': P(None, None, 'model')}
    row = {'kernel': P(None, 'model', None)}
    if cfg.attention_bias:
        # Stacked [L, out] biases shard with their column-parallel kernels.
        qkv = {'kernel': P(None, None, 'model'), 'bias': P(None, 'model')}
    else:
        qkv = col
    specs = {
        'embed': P(None, None),
        'layers': {
            'q': dict(qkv),
            'k': dict(qkv),
            'v': dict(qkv),
            'o': dict(row),
            'attn_ln': {'scale': P(None)},
            'gate': dict(col),
            'up': dict(col),
            'down': dict(row),
            'mlp_ln': {'scale': P(None)},
        },
        'final_ln': {'scale': P()},
    }
    if getattr(cfg, 'post_norms', False):
        specs['layers']['post_attn_ln'] = {'scale': P(None)}
        specs['layers']['post_mlp_ln'] = {'scale': P(None)}
    has_lm_head = (
        'lm_head' in params if params is not None else not cfg.tie_word_embeddings
    )
    if has_lm_head:
        specs['lm_head'] = P(None, 'model')
    return specs


def params_from_hf(state: dict[str, np.ndarray], cfg: MistralConfig) -> dict:
    """Convert HF ``MistralForCausalLM``/``MistralModel`` weights.

    Each stacked ``[L, ...]`` leaf is filled layer by layer straight from
    the checkpoint arrays, so the host holds the checkpoint plus ONE
    stacked copy — a per-layer list stacked afterwards is a third copy,
    43 GB at 7B widths, more than a 40 GiB one-chip host has."""
    from concurrent.futures import ThreadPoolExecutor

    sd = {k.removeprefix('model.'): v for k, v in state.items()}
    num_layers = cfg.num_layers

    def stacked(suffix, transpose=False):
        first = sd[f'layers.0.{suffix}']
        shape = first.shape[::-1] if transpose else first.shape
        out = np.empty((num_layers, *shape), first.dtype)

        def fill(i):
            leaf = sd[f'layers.{i}.{suffix}']
            out[i] = leaf.T if transpose else leaf

        # A transposing copy runs at ~0.1 GB/s on one core (14 GB at 7B
        # widths); numpy releases the GIL inside it, so layers fill in
        # parallel.
        with ThreadPoolExecutor(max_workers=8) as pool:
            list(pool.map(fill, range(num_layers)))
        return out

    def lin(name, bias_ok=False):  # torch Linear [out, in] -> [in, out]
        out = {'kernel': stacked(f'{name}.weight', transpose=True)}
        if f'layers.0.{name}.bias' in sd:
            if not bias_ok:
                # Only Q/K/V biases flow through the forward passes; a
                # checkpoint with e.g. an o_proj bias (HF Llama with
                # attention_bias=true) must fail loudly, not silently
                # drop the weight and diverge from HF.
                raise ValueError(
                    f'layers.0.{name}.bias: bias unsupported on this '
                    'projection'
                )
            out['bias'] = stacked(f'{name}.bias')
        return out

    layers = {
        'q': lin('self_attn.q_proj', bias_ok=True),
        'k': lin('self_attn.k_proj', bias_ok=True),
        'v': lin('self_attn.v_proj', bias_ok=True),
        'o': lin('self_attn.o_proj'),
        'attn_ln': {'scale': stacked('input_layernorm.weight')},
        'gate': lin('mlp.gate_proj'),
        'up': lin('mlp.up_proj'),
        'down': lin('mlp.down_proj'),
        'mlp_ln': {'scale': stacked('post_attention_layernorm.weight')},
    }
    params = {
        'embed': sd['embed_tokens.weight'],
        'layers': layers,
        'final_ln': {'scale': sd['norm.weight']},
    }
    if 'lm_head.weight' in state and not cfg.tie_word_embeddings:
        params['lm_head'] = np.ascontiguousarray(state['lm_head.weight'].T)
    return params
