"""Falcon-H1 decoders (``model_type falcon_h1``: tiiuae Falcon-H1 0.5B to
34B): in EVERY layer a Mamba-2 mixer and a grouped-query attention mixer
read one normed input and their outputs are added; a dense SwiGLU MLP; muP
multipliers on the embedding, on both mixers' inputs and outputs, on the
keys, on the five parts of the Mamba in-projection, inside the MLP and on
the logits; an output head of its own.

The layers are equal, so the parameters are ONE stacked tree
(``params['layers']``, ``[L, ...]`` a leaf). A sequence holds, of every
layer, BOTH K/V pages (one full-context paged group over all ``num_layers``
layers; the pool row is ``num_kv_heads * head_dim`` lanes, and a page's K is
``rope(k * key_multiplier)``) and a fixed recurrent state
(``cfg.state_spec()``): the SSM state ``[heads, head_dim, d_state]`` in
float32 and the last ``d_conv - 1`` inputs of the convolution in the model's
dtype. The state pool is a tuple of one ``[slots, ...]`` buffer a layer and
kind. Prefill runs the layers as one ``lax.scan`` (the K/V pool is addressed
by the layer index inside it, the rows of the slots it runs are gathered
before it and scattered after it); the decode window walks the layers
unrolled, because each layer's state is a buffer of its own that the step
rewrites whole and in place (a scan would have to address a tuple).

The Mamba-2 mixer is ``models/granite_hybrid.py``'s (``mamba_span``,
``mamba_step``), which takes the group count, the inner width and the
five-part multiplier from this config. A layer on ``x [T, hidden]``
(transformers ``models/falcon_h1``)::

    h = rms(x; input_layernorm)
    Mamba-2: p = (h * ssm_in_multiplier) W_in
             [z | x | B | C | dt] = p * ssm_multipliers (a factor a part)
             [x | B | C] = silu(causal_conv([x | B | C]) + b)
             dt = softplus(dt + dt_bias); A = -exp(A_log)
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t
             (head i reads the B, C of group i // (heads / groups))
             m = (rms over each group's channels of (y * silu(z)) * w) W_out
                 * ssm_out_multiplier
    attention: q = (h * attention_in_multiplier) W_q
               k = (h W_k) * key_multiplier; v = h W_v
               rope over all dims of q and k (rotate-half), causal,
               scores / sqrt(head_dim)
               a = (attn W_o) * attention_out_multiplier
    x = x + m + a;  h2 = rms(x; pre_ff_layernorm)
    x = x + (W_down(silu(W_gate h2 * mlp_multipliers[0]) * W_up h2))
            * mlp_multipliers[1]
    logits = (rms(x; final_layernorm) W_head) * lm_head_multiplier

Every multiplier is applied where the equations put it, in the model's
dtype (``lm_head_multiplier`` on the float32 logits); none is folded into a
weight. There is no ``params_from_hf`` yet.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from distllm_tpu.models import common
from distllm_tpu.models.granite_hybrid import (
    _gather_state,
    _scatter_state,
    mamba_leaf,
    mamba_span,
    mamba_step,
)
from distllm_tpu.utils import BaseConfig

F32 = jnp.float32


class FalconH1Config(BaseConfig):
    name: Literal['falcon_h1'] = 'falcon_h1'
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_ssm: int = 4096  # the mixer's inner width, stated by the family
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # factors of the in-projection's parts z, x, B, C, dt
    ssm_multipliers: tuple[float, float, float, float, float] | None = None
    # on the gate's pre-activation, on the MLP's output
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: str = 'bfloat16'

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def num_paged_layers(self) -> int:
        """Layers that own KV pages: all of them."""
        return self.num_layers

    @property
    def d_inner(self) -> int:
        return self.mamba_d_ssm

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def state_spec(self) -> dict:
        """What one sequence holds beside its KV pages: of EVERY layer the
        SSM state (float32: that is this module's, not a setting) and the
        convolution's last ``d_conv - 1`` inputs (the model's dtype)."""
        ssm = jax.ShapeDtypeStruct(
            (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state), F32
        )
        conv = jax.ShapeDtypeStruct(
            (self.mamba_d_conv - 1, self.conv_dim), jnp.dtype(self.dtype)
        )
        return {
            'ssm': (ssm,) * self.num_layers, 'conv': (conv,) * self.num_layers
        }

    def cache_spec(self) -> common.CacheSpec:
        """One full-context K/V group over ALL layers and a state leaf pair
        for ALL layers (a layer holds pages and state at once), this
        module's programs and no dense prefill: one family of programs
        carries the state from span to span."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_layers),),
            state=self.state_spec(),
            programs=__name__,
            program_prefix='falcon_h1_',
            dense_prefill=False,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'FalconH1Config':
        """The published keys as they are; values this module does not
        implement are refused by name."""
        heads, d_head = hf['mamba_n_heads'], hf['mamba_d_head']
        d_ssm = hf.get('mamba_d_ssm') or hf.get('mamba_expand', 2) * hf['hidden_size']
        groups = hf.get('mamba_n_groups', 1)
        biases = ('attention_bias', 'mamba_proj_bias', 'mlp_bias', 'projectors_bias')
        refusals = (
            ('mamba_d_ssm', d_ssm != heads * d_head,
             'an inner width that is not mamba_n_heads * mamba_d_head'),
            ('mamba_n_groups', bool(heads % groups),
             'a group count that does not divide mamba_n_heads'),
            *((key, bool(hf.get(key, False)), 'projection biases') for key in biases),
            ('mamba_conv_bias', not hf.get('mamba_conv_bias', True),
             'a convolution without its bias'),
            ('mamba_rms_norm', not hf.get('mamba_rms_norm', True),
             'a mixer without its gated norm'),
            ('mamba_norm_before_gate', bool(hf.get('mamba_norm_before_gate', False)),
             'the norm before the gate'),
            ('rope_scaling', hf.get('rope_scaling') is not None,
             'a scaled rotation'),
            ('tie_word_embeddings', bool(hf.get('tie_word_embeddings', False)),
             'a head tied to the embedding'),
            ('attn_layer_indices', hf.get('attn_layer_indices') is not None,
             'attention in some layers only'),
            ('hidden_act', hf.get('hidden_act', 'silu') != 'silu',
             'another activation than silu'),
        )
        for key, refused, what in refusals:
            if refused:
                raise ValueError(
                    f'falcon_h1: {key}={hf.get(key)!r} is not implemented '
                    f'({what})'
                )
        kv_heads = hf.get('num_key_value_heads', hf['num_attention_heads'])
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_layers=hf['num_hidden_layers'],
            num_heads=hf['num_attention_heads'],
            num_kv_heads=kv_heads,
            head_dim=hf.get('head_dim') or hf['hidden_size'] // hf['num_attention_heads'],
            intermediate_size=hf['intermediate_size'],
            mamba_n_heads=heads,
            mamba_d_head=d_head,
            mamba_d_ssm=d_ssm,
            mamba_d_state=hf['mamba_d_state'],
            mamba_n_groups=groups,
            mamba_d_conv=hf['mamba_d_conv'],
            mamba_chunk_size=hf.get('mamba_chunk_size', 128),
            embedding_multiplier=float(hf.get('embedding_multiplier', 1.0)),
            lm_head_multiplier=float(hf.get('lm_head_multiplier', 1.0)),
            attention_in_multiplier=float(hf.get('attention_in_multiplier', 1.0)),
            attention_out_multiplier=float(hf.get('attention_out_multiplier', 1.0)),
            key_multiplier=float(hf.get('key_multiplier', 1.0)),
            ssm_in_multiplier=float(hf.get('ssm_in_multiplier', 1.0)),
            ssm_out_multiplier=float(hf.get('ssm_out_multiplier', 1.0)),
            ssm_multipliers=(
                tuple(float(m) for m in hf['ssm_multipliers'])
                if hf.get('ssm_multipliers') is not None else None
            ),
            mlp_multipliers=tuple(
                float(m) for m in hf.get('mlp_multipliers', (1.0, 1.0))
            ),
            rope_theta=float(hf.get('rope_theta', 1e11)),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-5),
            max_position_embeddings=hf.get('max_position_embeddings', 262144),
        )


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: FalconH1Config) -> dict:
    """``name -> shape`` of one layer's parameters (kernels ``[in, out]``)."""
    h, i = cfg.hidden_size, cfg.intermediate_size
    q_out = cfg.num_heads * cfg.head_size
    kv_out = cfg.num_kv_heads * cfg.head_size
    heads = cfg.mamba_n_heads
    return {
        'ln': (h,), 'mlp_ln': (h,),
        'q': (h, q_out), 'k': (h, kv_out), 'v': (h, kv_out), 'o': (q_out, h),
        'in_proj': (h, cfg.d_inner + cfg.conv_dim + heads),
        'conv': (cfg.mamba_d_conv, cfg.conv_dim), 'conv_bias': (cfg.conv_dim,),
        'dt_bias': (heads,), 'A_log': (heads,), 'D': (heads,),
        'norm': (cfg.d_inner,), 'out_proj': (cfg.d_inner, h),
        'gate': (h, i), 'up': (h, i), 'down': (i, h),
    }


_SCALES = ('ln', 'mlp_ln', 'norm')  # {'scale': ...} leaves
_VECTORS = ('conv', 'conv_bias', 'dt_bias', 'A_log', 'D')  # bare leaves


def _wrap(name: str, leaf):
    if name in _SCALES:
        return {'scale': leaf}
    if name in _VECTORS:
        return leaf
    return {'kernel': leaf}


def _layout(cfg: FalconH1Config) -> tuple[dict, dict]:
    """``common.seeded_tree``'s tables: the top-level shapes, and the one
    tree's ``(fold-in number, layers, leaf shapes)``."""
    h, v = cfg.hidden_size, cfg.vocab_size
    return (
        {'embed': (v, h), 'head': (h, v)},
        {'layers': (2, cfg.num_layers, _layer_shapes(cfg))},
    )


def init_on_device(rng: jax.Array, cfg: FalconH1Config) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels, embedding and head, unit norm scales and ``D``, the
    mixer's leaves by ``granite_hybrid.mamba_leaf``, one RNG call per
    parameter kind. The published multipliers presume muP-sized weights: a
    driver that wants the mixers' mechanisms to show scales the kinds of
    leaf itself."""
    params = common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size, *_layout(cfg), _wrap,
        (*_SCALES, 'D'), mamba_leaf,
    )
    return {**params, 'head': {'kernel': params['head']}}


def param_specs(cfg: FalconH1Config, params: dict | None = None) -> dict:
    """Everything replicated: the engine refuses a mesh for a model with
    state, so there is no partitioning to state."""
    specs = common.tree_specs(*_layout(cfg), _wrap)
    return {**specs, 'head': {'kernel': specs['head']}}


def params_from_hf(state: dict, cfg: FalconH1Config) -> dict:
    raise NotImplementedError(
        'falcon_h1: no converter from a published checkpoint yet (the '
        'tensor names could not be read where this was written; it has to '
        'transpose the Linear weights, turn the depthwise conv weight [C, 1, '
        'K] into taps [K, C] and stack the layers); serve seeded weights '
        '(init_on_device)'
    )


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.rms_norm_eps)


def _times(x, multiplier: float):
    """``x * multiplier`` in ``x``'s dtype; nothing for a factor of one."""
    return x if multiplier == 1.0 else x * jnp.asarray(multiplier, x.dtype)


def _embed(params, cfg, input_ids):
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.asarray(params['embed'])[input_ids].astype(dtype)
    return _times(x, cfg.embedding_multiplier)


def _rope_tables(cfg: FalconH1Config, max_len: int):
    cos, sin = common.rope_frequencies(cfg.head_size, max_len, cfg.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def _qkv(h, lp, cfg, cos, sin, positions):
    """``h [B, S, hidden]`` (normed) -> ``q [B, S, H, d]``, ``k``, ``v [B,
    S, H_kv, d]``: the key's multiplier, then the rotation; what a page
    holds of a token is this ``k`` and ``v``."""
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_size)  # noqa: E731
    q = common.dense(_times(h, cfg.attention_in_multiplier), lp['q']['kernel'])
    k = _times(common.dense(h, lp['k']['kernel']), cfg.key_multiplier)
    v = common.dense(h, lp['v']['kernel'])
    q = common.apply_rope(heads(q, cfg.num_heads), cos, sin, positions)
    k = common.apply_rope(heads(k, cfg.num_kv_heads), cos, sin, positions)
    return q, k, heads(v, cfg.num_kv_heads)


def _attn_out(attn, lp, cfg):
    out = common.dense(
        attn.reshape(*attn.shape[:-2], cfg.num_heads * cfg.head_size),
        lp['o']['kernel'],
    )
    return _times(out, cfg.attention_out_multiplier)


def _finish_layer(x, mamba, attn, lp, cfg):
    """Both mixers' outputs onto the residual, then the MLP block."""
    x = x + _times(mamba, cfg.ssm_out_multiplier) + attn
    normed = _norm(x, lp['mlp_ln']['scale'], cfg)
    with jax.named_scope('distllm.dense_mlp'):
        gate = _times(
            common.dense(normed, lp['gate']['kernel']), cfg.mlp_multipliers[0]
        )
        mlp = common.dense(
            common.silu(gate) * common.dense(normed, lp['up']['kernel']),
            lp['down']['kernel'],
        )
    return x + _times(mlp, cfg.mlp_multipliers[1])


def logits(params: dict, cfg: FalconH1Config, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; the head is its own matrix, and
    ``lm_head_multiplier`` scales the float32 logits."""
    out = common.dense(hidden, params['head']['kernel']).astype(F32)
    return _times(out, cfg.lm_head_multiplier)


def _head(params, cfg, x):
    """Final norm and output head of ``x [..., hidden]``."""
    with jax.named_scope('distllm.head'):
        return logits(params, cfg, _norm(x, params['final_ln']['scale'], cfg))


# ----------------------------------------------------------------- forwards
def apply(  # distlint: traced
    params: dict,
    cfg: FalconH1Config,
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
    spec = cfg.state_spec()
    ssm0 = jnp.zeros((b, *spec['ssm'][0].shape), F32)
    conv0 = jnp.zeros((b, *spec['conv'][0].shape), spec['conv'][0].dtype)

    def layer(x, lp):
        h = _norm(x, lp['ln']['scale'], cfg)
        mamba, _, _ = mamba_span(
            _times(h, cfg.ssm_in_multiplier), lp, cfg, ssm0, conv0, tail_lens
        )
        q, k, v = _qkv(h, lp, cfg, cos, sin, None)
        attn = _attn_out(common.sdpa(q, k, v, mask=mask), lp, cfg)
        return _finish_layer(x, mamba, attn, lp, cfg), None

    x, _ = jax.lax.scan(layer, _embed(params, cfg, input_ids), params['layers'])
    return _norm(x, params['final_ln']['scale'], cfg)


def _span_state(state, slots, fresh, n):
    """The rows of ``slots`` of all ``n`` layers, stacked ``[n, B, ...]``
    for the layer scan; zeros for a row whose span is its sequence's first,
    whatever its slot held."""
    ssm0 = _gather_state(state, 'ssm', 0, n, slots)
    conv0 = _gather_state(state, 'conv', 0, n, slots)
    ssm0 = jnp.where(fresh[None, :, None, None, None], 0.0, ssm0)
    conv0 = jnp.where(fresh[None, :, None, None], 0, conv0)
    return ssm0, conv0


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: FalconH1Config,
    input_ids: jnp.ndarray,  # [B, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache: jnp.ndarray,  # [L, num_blocks, block_size, N_kv * Hd]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    state: dict,  # the state pool: per layer [slots, ...]
    slots: jnp.ndarray,  # [B] each row's slot (past the pool = pad row)
    max_table_positions: int | None = None,
    attn_backend: str = 'xla',
):
    """One span of every row through the paged path: a whole prompt, or
    one chunk of a long one with the state of the chunk before it. Every
    layer writes the rows' pages AND their state slots. A span that starts
    at position 0 starts from zero state, whatever its slot held: that is
    how a slot is zeroed when a sequence takes it. Returns ``(last_logits
    [B, V] float32, k_cache, v_cache, state)``."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention,
        write_chunk_kv,
    )

    s = input_ids.shape[1]
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    fresh = positions[:, 0] == 0
    cos, sin = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    n = cfg.num_layers
    ssm0, conv0 = _span_state(state, slots, fresh, n)

    def layer(carry, xs):
        x, k_cache, v_cache = carry
        li, lp, ssm0, conv0 = xs
        h = _norm(x, lp['ln']['scale'], cfg)
        mamba, ssm, conv = mamba_span(
            _times(h, cfg.ssm_in_multiplier), lp, cfg, ssm0, conv0, tail_lens
        )
        with jax.named_scope('distllm.attn_full'):
            q, k, v = _qkv(h, lp, cfg, cos, sin, positions)
            # the stacked pools whole, with the layer whose pages are meant
            k_cache, v_cache = write_chunk_kv(
                k_cache, v_cache, k, v, block_tables, positions, valid, layer=li
            )
            attn = ragged_paged_attention(
                q, k_cache, v_cache, block_tables, context_lens, positions,
                q_lens=tail_lens, backend=attn_backend, layer=li,
            )
            attn = _attn_out(attn, lp, cfg)
        x = _finish_layer(x, mamba, attn, lp, cfg)
        return (x, k_cache, v_cache), (ssm, conv)

    (x, k_cache, v_cache), (ssm, conv) = jax.lax.scan(
        layer, (_embed(params, cfg, input_ids), k_cache, v_cache),
        (jnp.arange(n, dtype=jnp.int32), params['layers'], ssm0, conv0),
    )
    state = _scatter_state(state, 'ssm', 0, ssm, slots)
    state = _scatter_state(state, 'conv', 0, conv, slots)
    last_x = common.last_token(x, tail_lens)
    return _head(params, cfg, last_x)[:, 0], k_cache, v_cache, state


def _decode_core(
    params, cfg, rope, attn_backend, input_ids, positions, context_lens,
    caches, block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first four arguments are bound; ``caches`` is ``(k_cache, v_cache,
    state)``). The layers are walked unrolled: each layer's state is a
    buffer of its own, rewritten whole and in place (row ``i`` of the batch
    is slot ``i``), and a static slice of the stacked kernels folds into its
    matmul. The count it returns is the live rows: those whose slots this
    step read and wrote."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    cos, sin = rope
    k_cache, v_cache, state = caches
    x = _embed(params, cfg, input_ids)  # [B, hidden]
    ssms, convs = list(state['ssm']), list(state['conv'])
    for li in range(cfg.num_layers):
        lp = common.layer_at(params['layers'], li)
        h = _norm(x, lp['ln']['scale'], cfg)
        mamba, ssms[li], convs[li] = mamba_step(
            _times(h, cfg.ssm_in_multiplier), lp, cfg, ssms[li], convs[li], live
        )
        with jax.named_scope('distllm.attn_full'):
            q, k, v = _qkv(h[:, None], lp, cfg, cos, sin, positions[:, None])
            k_cache, v_cache = write_token_kv(
                k_cache, v_cache, k[:, 0], v[:, 0], block_tables, positions,
                layer=li,
            )
            attn = decode_attention(
                q[:, 0], k_cache, v_cache, block_tables, context_lens,
                positions, backend=attn_backend, layer=li,
            )
            attn = _attn_out(attn, lp, cfg)
        x = _finish_layer(x, mamba, attn, lp, cfg)
    state = {'ssm': tuple(ssms), 'conv': tuple(convs)}
    rows = jnp.sum(live, dtype=jnp.int32)
    return _head(params, cfg, x), (k_cache, v_cache, state), rows


def decode_loop(  # distlint: traced
    params: dict,
    cfg: FalconH1Config,
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
    k_cache, v_cache, last_ids, state, counters)``; ``counters`` is
    ``{'state_rows': int32}``, the live rows summed over the window's
    steps: each read and wrote its slot in every layer."""
    rope = _rope_tables(cfg, max_table_positions or cfg.max_position_embeddings)
    tokens, (k_cache, v_cache, state), ids, state_rows = common.decode_window(
        functools.partial(_decode_core, params, cfg, rope, attn_backend),
        input_ids, positions, context_lens, (k_cache, v_cache, state),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=jnp.zeros((), jnp.int32),
    )
    return tokens, k_cache, v_cache, ids, state, {'state_rows': state_rows}
