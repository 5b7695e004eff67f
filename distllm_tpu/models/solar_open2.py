"""Solar-Open2 decoders (``model_type solar_open2``: upstage Solar-Open2):
Kimi-delta linear attention (KDA, arXiv:2510.26692) in three layers of four
beside one gated grouped-query attention layer with no positional encoding,
and in EVERY layer sigmoid-routed experts plus one shared expert.

Two kinds of mixer, so two stacked trees (``params['kda']``,
``params['gqa']``), and one tree for every layer's experts
(``params['moe']``: the banks stay stacked, ``models/moe.py`` picks the layer
inside the expert matmuls). A sequence holds K/V pages for the attention
layers only and, for every KDA layer, a slot of the state pool
(``cfg.state_spec()``): the matrix state ``[H, d_k, d_v]`` in float32 and the
last ``K - 1`` inputs of the three convolutions ``[K - 1, 3 H d_k]`` (q's,
k's, v's side by side) in the model's dtype. Prefill and the decode window
walk the layers unrolled, each kind traced once (``common.once_a_kind``).

A layer on ``x [T, hidden]``::

    u = rms(x; ln)
    KDA: q~, k~, v~ = u W_q, u W_k, u W_v   each through a causal depthwise
         convolution of K taps and SiLU; q = l2norm_head(q') / sqrt(d_k),
         k = l2norm_head(k')
         g_t = -exp(A_log_h) softplus(u W_f1 W_f2 + dt_bias)      [H, d_k]
         b_t = 2 sigmoid(u W_b)   (1 sigmoid without kda_allow_neg_eigval)
         S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
         o_t = S_t^T q_t                                  (``ops/kda.py``)
         m = (rms_head(o_t; w) * sigmoid(u W_g1 W_g2 + c)) W_o
    GQA: causal softmax at 1 / sqrt(head_dim) through the paged pool, no
         rotation; m = (attn * sigmoid(u W_gate)) W_o
    x = x + m;  n = rms(x; mlp_ln)
    x = x + routed(n) + shared(n)       sigmoid scores, the k largest of
         score + bias kept, kept scores over their sum, times
         routed_scaling_factor; the chip adds the pairs whose expert it holds

What computes a KDA layer follows from the backend and the call's static
shapes, by two pure rules of ``ops/kda.py``, never from a setting. The way in
(``_kda_inputs``: q, k, v of the projections' outputs): one Pallas kernel on a
TPU where a head is whole lane tiles of 128 and the span whole sublane tiles
of 16 (``kda.inputs_form``: every prefill program of the published widths),
``_qkv_xla`` everywhere else (a decode step's one position, a ragged span,
the other backends; the kernel's definition in the tests). The recurrence:
``kda.span_form`` for a span, ``kda_step`` for a decode step.
``SolarOpen2Config.prefill_forms`` tells the engine's telemetry both answers
for each prefill program.

There is no ``params_from_hf`` yet.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import common
from distllm_tpu.models.lfm2 import _taps
from distllm_tpu.models.moe import routed_experts
from distllm_tpu.ops import kda
from distllm_tpu.utils import BaseConfig

F32 = jnp.float32
_BANKS = ('gate', 'up', 'down')
_TREES = ('kda', 'gqa', 'moe')
_SCALES = ('ln', 'mlp_ln', 'o_norm')  # {'scale': ...}
_BARE = ('A_log', 'dt_bias', 'g_bias')  # float32 vectors, bare leaves
L2_EPS = 1e-6  # under the root of a head's squared norm (fla's l2norm)


class SolarOpen2Config(BaseConfig):
    name: Literal['solar_open2'] = 'solar_open2'
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_layers: int = 48
    gqa_layers: tuple[int, ...] = (0,)  # the layers with the attention mixer
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4  # taps of the short convolutions
    kda_neg_eigval: bool = True  # beta in (0, 2) and not (0, 1)
    moe_intermediate_size: int = 1280  # width of a routed and of the shared expert
    # The router ranks num_experts; this chip holds num_local_experts of
    # them, ids first_local_expert onward (all of them by default).
    num_experts: int = 320
    num_local_experts: int = 320
    first_local_expert: int = 0
    experts_per_token: int = 8
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    dtype: str = 'bfloat16'

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def num_paged_layers(self) -> int:
        """Layers that own KV pages: the attention layers."""
        return self.count('gqa')

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def count(self, kind: str) -> int:
        """Layers of a parameter tree."""
        gqa = sum(1 for li in range(self.num_layers) if li in self.gqa_layers)
        return {
            'kda': self.num_layers - gqa, 'gqa': gqa, 'moe': self.num_layers
        }[kind]

    def layer_indices(self) -> list[tuple[str, int]]:
        """``(mixer, index in the mixer's tree)`` of every layer, in order;
        a layer's index in the experts' tree is its own."""
        out, seen = [], {'kda': 0, 'gqa': 0}
        for li in range(self.num_layers):
            mixer = 'gqa' if li in self.gqa_layers else 'kda'
            out.append((mixer, seen[mixer]))
            seen[mixer] += 1
        return out

    def state_spec(self) -> dict:
        """What one sequence holds beside its KV pages: per KDA layer the
        matrix state (float32: that is this module's, not a setting) and
        the three convolutions' last ``kda_conv - 1`` inputs (the model's
        dtype)."""
        n = self.count('kda')
        matrix = jax.ShapeDtypeStruct(
            (self.kda_heads, self.kda_head_dim, self.kda_head_dim), F32
        )
        conv = jax.ShapeDtypeStruct(
            (self.kda_conv - 1, 3 * self.kda_width), jnp.dtype(self.dtype)
        )
        return {'kda': (matrix,) * n, 'conv': (conv,) * n}

    def prefill_forms(self, programs: dict) -> dict:
        """What the engine's telemetry says of this family's prefill
        programs (``programs``: name -> (span, rows)): under
        ``'kda_span_form'`` what computes the span form of the delta rule
        in each (``ops.kda.span_form``, the rule ``kda_span`` itself traces
        with): the kernel's (chunk, sub-block, heads a grid step), or
        ``'xla'``; under ``'kda_inputs_form'`` what makes q, k and v of the
        projections' output (``ops.kda.inputs_form``, ``_kda_inputs``'
        rule): the kernel's (sequence tile, rows a step, channel tile), or
        ``'xla'``."""
        backend = kda.span_backend()
        heads, head = self.kda_heads, self.kda_head_dim
        return {
            'kda_span_form': {
                key: kda.span_form(backend, rows, span, heads, head, head)
                for key, (span, rows) in programs.items()
            },
            'kda_inputs_form': {
                key: kda.inputs_form(
                    backend, rows, span, 3 * self.kda_width, self.kda_conv, head
                )
                for key, (span, rows) in programs.items()
            },
        }

    def cache_spec(self) -> common.CacheSpec:
        """K/V pages for the attention layers, the KDA layers' state beside
        them (they hold no pages), this module's programs and no dense
        prefill: one family of programs carries the state from span to
        span."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_paged_layers),),
            state=self.state_spec(),
            programs=__name__,
            program_prefix='solar_open2_',
            dense_prefill=False,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'SolarOpen2Config':
        """The published keys as they are, plus two that state a chip's
        share as the other expert families' configurations do
        (``num_routed_experts``: the router's width where
        ``n_routed_experts`` counts the experts held;
        ``first_local_expert``). Values this module does not implement are
        refused by name."""
        linear = hf['linear_attn_config']
        use_rope = bool(hf.get('use_rope', False))
        refusals = (
            ('kda_use_full_proj', bool(hf.get('kda_use_full_proj', False)),
             'full-rank decay and gate projections'),
            ('partial_rotary_factor',
             use_rope and hf.get('partial_rotary_factor', 1) != 1,
             'a rotation over part of a head'),
            ('use_rope', use_rope, 'a rotation in the attention layers'),
            ('first_k_dense_replace', hf.get('first_k_dense_replace', 0) > 0,
             'leading layers with a dense MLP'),
            ('n_shared_experts', hf.get('n_shared_experts', 1) != 1,
             'another number of shared experts than one'),
            ('use_gqa_gate', not hf.get('use_gqa_gate', True),
             'an attention layer without its output gate'),
            ('norm_topk_prob', not hf.get('norm_topk_prob', True),
             'kept scores left unnormalised'),
            ('tie_word_embeddings', bool(hf.get('tie_word_embeddings', False)),
             'a head tied to the embedding'),
            ('linear_attn_config',
             linear.get('num_kv_heads') not in (None, linear['num_heads']),
             'fewer key heads than heads in the KDA layers'),
        )
        for key, refused, what in refusals:
            if refused:
                raise ValueError(
                    f'solar_open2: {key}={hf.get(key)!r} is not implemented '
                    f'({what})'
                )
        held = hf['n_routed_experts']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_layers=hf['num_hidden_layers'],
            gqa_layers=tuple(hf['gqa_layers']),
            num_heads=hf['num_attention_heads'],
            num_kv_heads=hf.get('num_key_value_heads', hf['num_attention_heads']),
            head_dim=hf.get('head_dim') or hf['hidden_size'] // hf['num_attention_heads'],
            kda_heads=linear['num_heads'],
            kda_head_dim=linear['head_dim'],
            kda_conv=linear['short_conv_kernel_size'],
            kda_neg_eigval=bool(hf.get('kda_allow_neg_eigval', False)),
            moe_intermediate_size=hf['moe_intermediate_size'],
            num_experts=hf.get('num_routed_experts', held),
            num_local_experts=held,
            first_local_expert=hf.get('first_local_expert', 0),
            experts_per_token=hf['num_experts_per_tok'],
            routed_scaling_factor=float(hf.get('routed_scaling_factor', 1.0)),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-5),
            max_position_embeddings=hf.get('max_position_embeddings', 1048576),
        )


# ------------------------------------------------------------- parameters
def _tree_shapes(cfg: SolarOpen2Config, kind: str) -> dict:
    """``name -> shape`` of one layer's parameters in the tree ``kind``
    (kernels ``[in, out]``)."""
    h = cfg.hidden_size
    if kind == 'kda':
        w, r = cfg.kda_width, cfg.kda_head_dim  # the gates' low rank: a head
        return {
            'ln': (h,), 'q': (h, w), 'k': (h, w), 'v': (h, w),
            'conv': (cfg.kda_conv, 3 * w),
            'f_a': (h, r), 'f_b': (r, w), 'A_log': (cfg.kda_heads,),
            'dt_bias': (w,), 'b': (h, cfg.kda_heads),
            'g_a': (h, r), 'g_b': (r, w), 'g_bias': (w,),
            'o_norm': (cfg.kda_head_dim,), 'o': (w, h),
        }
    if kind == 'gqa':
        q_out = cfg.num_heads * cfg.head_dim
        kv_out = cfg.num_kv_heads * cfg.head_dim
        return {
            'ln': (h,), 'q': (h, q_out), 'k': (h, kv_out), 'v': (h, kv_out),
            'attn_gate': (h, q_out), 'o': (q_out, h),
        }
    i, e = cfg.moe_intermediate_size, cfg.num_local_experts
    return {
        'mlp_ln': (h,), 'router': (h, cfg.num_experts),
        'router_bias': (cfg.num_experts,),  # chooses, never weighs; float32
        'gate': (e, h, i), 'up': (e, h, i), 'down': (e, i, h),
        'shared_gate': (h, i), 'shared_up': (h, i), 'shared_down': (i, h),
    }


def _wrap(name: str, leaf):
    """``{'scale'}`` norms, ``{'taps'}`` the convolutions' ``[K, 3 H d_k]``
    weights (tap ``j`` multiplies ``u_{t - (K-1) + j}``), ``{'bias'}`` the
    router's selection bias, bare the float32 vectors, ``{'kernel'}`` the
    rest."""
    if name in _SCALES:
        return {'scale': leaf}
    if name in _BARE:
        return leaf
    return {{'conv': 'taps', 'router_bias': 'bias'}.get(name, 'kernel'): leaf}


def _trees(cfg: SolarOpen2Config) -> dict:
    return common.tree_table(
        _TREES, cfg.count, lambda kind: _tree_shapes(cfg, kind)
    )


def _top_shapes(cfg: SolarOpen2Config) -> dict:
    h, v = cfg.hidden_size, cfg.vocab_size
    return {'embed': (v, h), 'head': (h, v)}


# The decay's leaves, as the published KDA initialisation draws them: A
# uniform in [1, 16] a head, dt log-uniform in [0.001, 0.1] a channel
# (``dt_bias`` its inverse softplus). A step's decay ``exp(-A dt)`` then
# runs from 0.9999 (a channel that remembers thousands of tokens) down to
# 0.2 (one that forgets in two): both ends of what the chunk form has to
# carry.
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def kda_leaf(name, key, shape, normal, taps: int = 4):
    """``common.seeded_tree``'s rule for this family's leaves that are not
    normal(0, 0.02): the decay's (float32, ``A_RANGE``, ``DT_RANGE``), the
    gate's bias zero, taps normal(0, 1 / sqrt(K)) (the convolution's output
    has its input's size), the router's selection bias normal(0, 0.01) in
    float32 (a zero buffer before training, and in a trained router what
    balances the experts' loads. The 8th and 9th of 320 sigmoid scores lie
    0.005 apart, so at 0.01 the bias decides the kept set of most tokens;
    at 0.05 it makes an expert 3.7 times as popular a standard deviation,
    which no balanced router is, and a chip's share of the pairs then
    moves by a third from seed to seed)."""
    if name == 'A_log':
        return jnp.log(jax.random.uniform(key, shape, F32, *A_RANGE))
    if name == 'dt_bias':
        dt = jnp.exp(jax.random.uniform(key, shape, F32, *np.log(DT_RANGE)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    if name == 'g_bias':
        return jnp.zeros(shape, F32)
    if name == 'conv':
        return normal(key, shape, taps ** -0.5)
    if name == 'router_bias':
        return normal(key, shape, 0.01, F32)
    return None


def init_on_device(rng: jax.Array, cfg: SolarOpen2Config) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels, embedding and head, unit norm scales, the rest by
    ``kda_leaf``, one RNG call per parameter kind."""
    params = common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size, _top_shapes(cfg), _trees(cfg), _wrap,
        _SCALES, functools.partial(kda_leaf, taps=cfg.kda_conv),
    )
    return {**params, 'head': {'kernel': params['head']}}


def param_specs(cfg: SolarOpen2Config, params: dict | None = None) -> dict:
    """Expert banks over ``expert``, everything else replicated."""
    specs = common.tree_specs(
        _top_shapes(cfg), _trees(cfg), _wrap, [('moe', n) for n in _BANKS]
    )
    return {**specs, 'head': {'kernel': specs['head']}}


def params_from_hf(state: dict, cfg: SolarOpen2Config) -> dict:
    raise NotImplementedError(
        'solar_open2: no converter from a published checkpoint yet (the '
        'config gives no tensor names; by fla.layers.kda and the GLM-4.5 '
        'block it has to transpose the Linear weights, lay the three '
        'depthwise conv weights [C, 1, K] side by side as taps [K, 3 C], '
        'name the low-rank pairs f_proj.0/.1 and g_proj.0/.1 f_a/f_b and '
        'g_a/g_b, stack the held experts into banks and split the layers '
        'into their three trees); serve seeded weights (init_on_device)'
    )


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.rms_norm_eps)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _heads(t, cfg):
    return t.reshape(*t.shape[:-1], cfg.kda_heads, cfg.kda_head_dim)


def _qkv_xla(window, lp, cfg, s: int):
    """The way in as XLA programs, and its definition: the taps' sum over
    the convolutions' whole input in float32, SiLU, q's and k's L2 norm a
    head, q's scale. ``q, k, v [B, S, H, d]`` float32."""
    q, k, v = (
        _heads(t, cfg)
        for t in jnp.split(jax.nn.silu(_taps(window, lp, s)), 3, -1)
    )
    return _l2(q) * cfg.kda_head_dim ** -0.5, _l2(k), v


def _kda_inputs(u, lp, cfg, conv0):
    """What the recurrence reads of normed inputs ``u [B, S, hidden]`` that
    follow the carried convolution inputs ``conv0 [B, K - 1, 3 H d_k]``:
    ``q, k, v, g [B, S, H, d]`` and ``beta [B, S, H]`` in float32, and the
    convolutions' whole input ``[B, K - 1 + S, 3 H d_k]``. What makes ``q,
    k, v`` of the projections' output follows from the backend and the
    call's static shapes (``ops.kda.inputs_form``): one Pallas kernel on a
    TPU where the head is whole lane tiles and the span whole sublane tiles
    (every prefill program of the published widths), ``_qkv_xla``
    everywhere else (a decode step's one position, a ragged span, the
    other backends). The two differ by float32 rounding alone."""
    s = u.shape[1]
    projected = [common.dense(u, lp[n]['kernel']) for n in 'qkv']
    qkv = jnp.concatenate(projected, axis=-1)
    window = jnp.concatenate([conv0.astype(qkv.dtype), qkv], axis=1)
    backend = kda.span_backend()
    form = kda.inputs_form(
        backend, u.shape[0], s, qkv.shape[-1], cfg.kda_conv, cfg.kda_head_dim
    )
    if form == 'xla':
        q, k, v = _qkv_xla(window, lp, cfg, s)
    else:
        q, k, v = (_heads(t, cfg) for t in kda.inputs_kernel(
            projected, conv0, lp['conv']['taps'], form=form,
            head=cfg.kda_head_dim, q_scale=cfg.kda_head_dim ** -0.5,
            eps=L2_EPS, interpret=backend == 'interpret',
        ))
    low = common.dense(common.dense(u, lp['f_a']['kernel']), lp['f_b']['kernel'])
    g = -jnp.exp(lp['A_log'].astype(F32))[:, None] * _heads(
        jax.nn.softplus(low.astype(F32) + lp['dt_bias'].astype(F32)), cfg
    )
    beta = jax.nn.sigmoid(common.dense(u, lp['b']['kernel']).astype(F32))
    return q, k, v, g, beta * (2.0 if cfg.kda_neg_eigval else 1.0), window


def _conv_rows(window, tail_lens, keep: int):
    """``common.conv_tail(window, tail_lens, keep)`` without the whole
    input: a row's last ``keep`` counted rows lie among the ``keep`` carried
    rows and the ``keep`` of the span that end at its tail, so that narrow
    window is cut first, a static slice of the carried rows and a dynamic
    slice a row of each projection's output (XLA gives a slice of the
    concatenation the operand it came from), and ``conv_tail`` reads it.
    Where the kernel makes q, k and v nothing else reads ``window``: as a
    gather's operand it would be built, ``[B, K - 1 + S, 3 H d]``, and copied
    to the gather's layout (0.85 ms a ``(512, 4)`` dispatch and layer; chip,
    PR 47)."""
    if window.shape[1] < 2 * keep:  # a span shorter than the carried rows
        return common.conv_tail(window, tail_lens, keep)
    first = jnp.maximum(tail_lens - keep, 0)
    ending_at_the_tail = jnp.concatenate([
        jnp.concatenate([
            jax.lax.dynamic_slice(t, (b, first[b], 0), (1, keep, t.shape[-1]))
            for b in range(t.shape[0])
        ]) for t in jnp.split(window[:, keep:], 3, -1)  # q's, k's, v's
    ], axis=-1)
    near = jnp.concatenate([window[:, :keep], ending_at_the_tail], axis=1)
    return common.conv_tail(near, jnp.minimum(tail_lens, keep), keep)


def _kda_out(o, u, lp, cfg):
    """The recurrence's ``o [..., H, d_v]`` float32 through the norm a
    head, the sigmoid gate and the output projection."""
    with jax.named_scope('distllm.kda_out'):
        low = common.dense(
            common.dense(u, lp['g_a']['kernel']), lp['g_b']['kernel']
        )
        gate = jax.nn.sigmoid(low.astype(F32) + lp['g_bias'].astype(F32))
        normed = common.rms_norm(o, lp['o_norm']['scale'], cfg.rms_norm_eps)
        gated = normed.reshape(gate.shape) * gate
        return common.dense(gated.astype(u.dtype), lp['o']['kernel'])


def kda_mixer_span(u, lp, cfg, state0, conv0, tail_lens):  # distlint: traced
    """A KDA mixer over a span ``u [B, S, hidden]`` that starts from
    ``state0 [B, H, d_k, d_v]`` float32 and ``conv0 [B, K - 1, 3 H d_k]``
    and counts the first ``tail_lens [B]`` positions of each row. Returns
    the output and both states after each row's last counted position."""
    s = u.shape[1]
    with jax.named_scope('distllm.kda_proj'):
        q, k, v, g, beta, window = _kda_inputs(u, lp, cfg, conv0)
        valid = jnp.arange(s)[None, :] < tail_lens[:, None]
        # A position that does not count leaves the state as it is.
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
        conv = _conv_rows(window, tail_lens, conv0.shape[1])
    with jax.named_scope('distllm.kda_span'):
        o, state = kda.kda_span(q, k, v, g, beta, state0)
    return _kda_out(o, u, lp, cfg), state, conv.astype(conv0.dtype)


def kda_mixer_step(u, lp, cfg, state0, conv0, live):  # distlint: traced
    """One token of every row, ``u [B, hidden]``; rows that are not
    ``live`` keep their state."""
    with jax.named_scope('distllm.kda_proj'):
        q, k, v, g, beta, window = _kda_inputs(u[:, None], lp, cfg, conv0)
        conv = jnp.where(
            live[:, None, None], window[:, 1:].astype(conv0.dtype), conv0
        )
    with jax.named_scope('distllm.kda_step'):
        o, state = kda.kda_step(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state0
        )
        state = jnp.where(live[:, None, None, None], state, state0)
    return _kda_out(o, u, lp, cfg), state, conv


def _qkv(u, lp, cfg):
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_dim)  # noqa: E731
    return (
        heads(common.dense(u, lp['q']['kernel']), cfg.num_heads),
        heads(common.dense(u, lp['k']['kernel']), cfg.num_kv_heads),
        heads(common.dense(u, lp['v']['kernel']), cfg.num_kv_heads),
    )


def _attn_out(attn, u, lp, cfg):
    """``(attn * sigmoid(u W_gate)) W_o``: the gate elementwise on the
    heads' outputs, before the output projection (arXiv:2505.06708)."""
    gate = jax.nn.sigmoid(common.dense(u, lp['attn_gate']['kernel']).astype(F32))
    flat = attn.reshape(*attn.shape[:-2], cfg.num_heads * cfg.head_dim)
    return common.dense(
        (flat.astype(F32) * gate).astype(u.dtype), lp['o']['kernel']
    )


def _mlp(x, mp, cfg, counted, banks, li):
    """``routed(x) + shared(x)`` of one layer for ``x [T, hidden]``
    (already normed), and the layer's (routed, held) pair counts. ``banks``
    is the experts' tree: the banks stay stacked, ``li`` picks the layer
    inside the expert matmuls (``models/moe.py``)."""
    routed, pairs = routed_experts(
        x, mp['router']['kernel'], *(banks[n]['kernel'] for n in _BANKS),
        cfg.experts_per_token, first_expert=cfg.first_local_expert,
        counted=counted, layer=li, routed_scale=cfg.routed_scaling_factor,
        scoring='sigmoid', select_bias=mp['router_bias']['bias'],
    )
    # The shared expert: every chip of the expert axis computes it alike,
    # so it is counted once, here, whatever share of the bank is held.
    with jax.named_scope('distllm.moe'):
        shared = common.swiglu(
            x, mp['shared_gate']['kernel'], mp['shared_up']['kernel'],
            mp['shared_down']['kernel'],
        )
    return routed + shared, pairs


def _finish_layer(x, mixed, mp, cfg, counted, banks, li):
    """Residual of the mixer's output, then the experts' block."""
    return common.finish_layer(
        x, mixed, mp, cfg.rms_norm_eps,
        lambda rows, of_rows: _mlp(rows, mp, cfg, of_rows, banks, li),
        counted,
    )


def logits(params: dict, cfg: SolarOpen2Config, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; the head is its own matrix."""
    return common.dense(hidden, params['head']['kernel']).astype(F32)


def _head(params, cfg, x):
    """Final norm and output head of ``x [..., hidden]``."""
    with jax.named_scope('distllm.head'):
        return logits(params, cfg, _norm(x, params['final_ln']['scale'], cfg))


def _layer_params(params, mixer, xi, li):
    return (
        common.layer_at(params[mixer], xi),
        common.layer_at(params['moe'], li, skip=_BANKS),
    )


def _layer_fns(layers: dict, cfg: SolarOpen2Config) -> dict:
    """``(mixer,) -> layers[mixer](*arrays)``, jitted once a kind."""
    return common.once_a_kind(
        lambda mixer, *rest: layers[mixer](*rest),
        [(mixer,) for mixer, _ in cfg.layer_indices()],
        'solar_open2_{}_layer',
    )


# ----------------------------------------------------------------- forwards
def apply(  # distlint: traced
    params: dict,
    cfg: SolarOpen2Config,
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
    x = common.embed(params, cfg.dtype, input_ids)
    spec = cfg.state_spec()
    for li, (mixer, xi) in enumerate(cfg.layer_indices()):
        lp, mp = _layer_params(params, mixer, xi, li)
        u = _norm(x, lp['ln']['scale'], cfg)
        if mixer == 'kda':
            state0 = jnp.zeros((b, *spec['kda'][0].shape), F32)
            conv0 = jnp.zeros((b, *spec['conv'][0].shape), x.dtype)
            mixed, _, _ = kda_mixer_span(u, lp, cfg, state0, conv0, tail_lens)
        else:
            q, k, v = _qkv(u, lp, cfg)
            mixed = _attn_out(common.sdpa(q, k, v, mask=mask), u, lp, cfg)
        x, _ = _finish_layer(
            x, mixed, mp, cfg, valid, params['moe'], jnp.int32(li)
        )
    return _norm(x, params['final_ln']['scale'], cfg)


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: SolarOpen2Config,
    input_ids: jnp.ndarray,  # [B, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache: jnp.ndarray,  # [L_gqa, num_blocks, block_size, N_kv * d]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    state: dict,  # the state pool: per KDA layer [slots, ...]
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

    del max_table_positions  # no rotation: no table of positions
    s = input_ids.shape[1]
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    fresh = positions[:, 0] == 0
    matrices, convs = list(state['kda']), list(state['conv'])
    x = common.embed(params, cfg.dtype, input_ids)

    def kda_layer(x, lp, mp, banks, li, matrix_pool, conv_pool, slots, fresh,
                  tail_lens, valid):
        u = _norm(x, lp['ln']['scale'], cfg)
        state0 = jnp.where(fresh[:, None, None, None], 0.0, matrix_pool[slots])
        conv0 = jnp.where(fresh[:, None, None], 0, conv_pool[slots])
        mixed, matrix, conv = kda_mixer_span(u, lp, cfg, state0, conv0, tail_lens)
        # a pad row's slot lies past the pool: its write is dropped
        matrix_pool = matrix_pool.at[slots].set(matrix, mode='drop')
        conv_pool = conv_pool.at[slots].set(conv, mode='drop')
        x, _ = _finish_layer(x, mixed, mp, cfg, valid, banks, li)
        return x, matrix_pool, conv_pool

    def gqa_layer(x, lp, mp, banks, li, k_cache, v_cache, xi, table,
                  positions, valid, context_lens, tail_lens):
        u = _norm(x, lp['ln']['scale'], cfg)
        q, k, v = _qkv(u, lp, cfg)
        with jax.named_scope('distllm.attn_full'):
            # the stacked pools whole, with the layer whose pages are meant
            k_cache, v_cache = write_chunk_kv(
                k_cache, v_cache, k, v, table, positions, valid, layer=xi
            )
            attn = ragged_paged_attention(
                q, k_cache, v_cache, table, context_lens, positions,
                q_lens=tail_lens, backend=attn_backend, layer=xi,
            )
        x, _ = _finish_layer(
            x, _attn_out(attn, u, lp, cfg), mp, cfg, valid, banks, li
        )
        return x, k_cache, v_cache

    layer_of = _layer_fns({'kda': kda_layer, 'gqa': gqa_layer}, cfg)
    for li, (mixer, xi) in enumerate(cfg.layer_indices()):
        shared = (
            x, *_layer_params(params, mixer, xi, li), params['moe'],
            jnp.int32(li),
        )
        if mixer == 'kda':
            x, matrices[xi], convs[xi] = layer_of[mixer,](
                *shared, matrices[xi], convs[xi], slots, fresh, tail_lens, valid
            )
        else:
            x, k_cache, v_cache = layer_of[mixer,](
                *shared, k_cache, v_cache, jnp.int32(xi), block_tables,
                positions, valid, context_lens, tail_lens,
            )
    last_x = common.last_token(x, tail_lens)
    state = {'kda': tuple(matrices), 'conv': tuple(convs)}
    return _head(params, cfg, last_x)[:, 0], k_cache, v_cache, state


def _decode_core(
    params, cfg, attn_backend, input_ids, positions, context_lens, caches,
    block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first three arguments are bound; ``caches`` is ``(k_cache, v_cache,
    state)``). The layers are walked unrolled: each KDA layer's state is a
    buffer of its own, rewritten whole and in place (row ``i`` of the batch
    is slot ``i``), and a static slice of the stacked kernels folds into
    its matmul. Counts the step's (routed, held) pairs and its live rows:
    those whose slots this step read and wrote."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    k_cache, v_cache, state = caches
    x = common.embed(params, cfg.dtype, input_ids)  # [B, hidden]
    matrices, convs = list(state['kda']), list(state['conv'])
    pairs = jnp.zeros((2,), jnp.int32)

    def kda_layer(x, lp, mp, banks, li, matrix0, conv0, live):
        u = _norm(x, lp['ln']['scale'], cfg)
        mixed, matrix, conv = kda_mixer_step(u, lp, cfg, matrix0, conv0, live)
        x, layer_pairs = _finish_layer(x, mixed, mp, cfg, live, banks, li)
        return x, matrix, conv, layer_pairs

    def gqa_layer(x, lp, mp, banks, li, k_cache, v_cache, xi, table,
                  positions, context_lens, live):
        u = _norm(x, lp['ln']['scale'], cfg)
        q, k, v = _qkv(u, lp, cfg)
        with jax.named_scope('distllm.attn_full'):
            k_cache, v_cache = write_token_kv(
                k_cache, v_cache, k, v, table, positions, layer=xi
            )
            attn = decode_attention(
                q, k_cache, v_cache, table, context_lens, positions,
                backend=attn_backend, layer=xi,
            )
        x, layer_pairs = _finish_layer(
            x, _attn_out(attn, u, lp, cfg), mp, cfg, live, banks, li
        )
        return x, k_cache, v_cache, layer_pairs

    layer_of = _layer_fns({'kda': kda_layer, 'gqa': gqa_layer}, cfg)
    for li, (mixer, xi) in enumerate(cfg.layer_indices()):
        shared = (
            x, *_layer_params(params, mixer, xi, li), params['moe'],
            jnp.int32(li),
        )
        if mixer == 'kda':
            x, matrices[xi], convs[xi], layer_pairs = layer_of[mixer,](
                *shared, matrices[xi], convs[xi], live
            )
        else:
            x, k_cache, v_cache, layer_pairs = layer_of[mixer,](
                *shared, k_cache, v_cache, jnp.int32(xi), block_tables,
                positions, context_lens, live,
            )
        pairs = pairs + layer_pairs
    state = {'kda': tuple(matrices), 'conv': tuple(convs)}
    counts = {
        'moe_pairs': pairs[0], 'moe_pairs_held': pairs[1],
        'state_rows': jnp.sum(live, dtype=jnp.int32),
    }
    return _head(params, cfg, x), (k_cache, v_cache, state), counts


def decode_loop(  # distlint: traced
    params: dict,
    cfg: SolarOpen2Config,
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
    ``{'moe_pairs', 'moe_pairs_held', 'state_rows'}``, int32, summed over
    the rows and steps that ran."""
    del max_table_positions  # no rotation: no table of positions
    zero = jnp.zeros((), jnp.int32)
    tokens, (k_cache, v_cache, state), ids, counts = common.decode_window(
        functools.partial(_decode_core, params, cfg, attn_backend),
        input_ids, positions, context_lens, (k_cache, v_cache, state),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts={'moe_pairs': zero, 'moe_pairs_held': zero, 'state_rows': zero},
    )
    return tokens, k_cache, v_cache, ids, state, counts
