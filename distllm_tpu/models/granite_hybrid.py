"""Granite 4.0-H (``granitemoehybrid``): Mamba-2 mixers with an attention
mixer every few layers, a routed expert layer plus a shared gated MLP in
every layer, no positional encoding, Granite's four multipliers.

Two kinds of layer, so two stacked parameter trees (``params['mamba']``,
``params['attention']``); the forwards walk ``cfg.layer_types`` and scan each run of
equal layers. A sequence holds two kinds of state: K/V pages for the attention layers
(``PagedKVCache`` over ``cfg.num_paged_layers`` layers) and, for every Mamba layer, a
fixed recurrent state (``cfg.state_spec()``): the SSM state ``[heads, head_dim,
d_state]`` in float32 and the last ``d_conv - 1`` columns of the convolution's input
in the model's dtype. The state pool is a tuple of one array per Mamba layer,
``[slots, ...]``: the decode window updates whole buffers in place, prefill gathers
and scatters the rows of the slots it runs.

The attention mixers call the serving attention entry points that ``models/mistral.py``
calls (``common.sdpa``, ``ragged_paged_attention``, ``write_chunk_kv``,
``write_token_kv``) with ``scale=attention_multiplier`` and no rotation. The expert
layer is ``models/moe.py``: a chip may hold a share of the routed experts
(``first_local_expert``, ``num_local_experts``) while the router ranks all
``num_experts``. The Mamba-2 functions serve every family with such a mixer
(``models/falcon_h1.py``): what they take from the config they are given, groups of
``B, C`` and factors of the in-projection's parts among it, is said at this file's end.

Equations (transformers ``models/granitemoehybrid``):

    x = E[ids] * embedding_multiplier
    x = x + residual_multiplier * mixer(rms(x))
    x = x + residual_multiplier * (moe(rms(x)) + shared(rms(x)))
    logits = rms(x) @ E^T / logits_scaling

    Mamba-2: [z, xBC, dt] = h @ W_in; xBC = silu(causal_conv(xBC) + b)
             -> x, B, C; dt = softplus(dt + dt_bias); A = -exp(A_log)
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t
             out = (rms(y * silu(z)) * w) @ W_out
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import common
from distllm_tpu.models.moe import routed_experts
from distllm_tpu.utils import BaseConfig

F32 = jnp.float32


class GraniteHybridConfig(BaseConfig):
    name: Literal['granitemoehybrid'] = 'granitemoehybrid'
    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: tuple[Literal['mamba', 'attention'], ...] = ('mamba',)
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    intermediate_size: int = 768  # width of one routed expert
    shared_intermediate_size: int = 1536
    # The router ranks num_experts; this chip holds num_local_experts of
    # them, ids first_local_expert onward (all of them by default).
    num_experts: int = 72
    num_local_experts: int = 72
    first_local_expert: int = 0
    experts_per_token: int = 10
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    dtype: str = 'bfloat16'

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def num_paged_layers(self) -> int:
        """Layers that own KV pages: the attention layers."""
        return self.layer_types.count('attention')

    @property
    def num_mamba_layers(self) -> int:
        return self.layer_types.count('mamba')

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    def layer_runs(self) -> list[tuple[str, int, int]]:
        """``(kind, first index in its kind's tree, count)`` of every run of
        equal consecutive layers."""
        runs: list[list] = []
        seen = {'mamba': 0, 'attention': 0}
        for kind in self.layer_types:
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, seen[kind], 1])
            seen[kind] += 1
        return [tuple(r) for r in runs]

    def state_spec(self) -> dict:
        """What one sequence holds beside its KV pages: per Mamba layer the
        shape and dtype of its SSM state and of its convolution state. The
        SSM state is float32 and the convolution state the model's dtype;
        that is this module's, not a setting."""
        n = self.num_mamba_layers
        ssm = jax.ShapeDtypeStruct(
            (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state), F32
        )
        conv = jax.ShapeDtypeStruct(
            (self.mamba_d_conv - 1, self.conv_dim), jnp.dtype(self.dtype)
        )
        return {'ssm': (ssm,) * n, 'conv': (conv,) * n}

    def cache_spec(self) -> common.CacheSpec:
        """K/V pages for the attention layers, the recurrent state beside
        them, this module's programs, and no dense prefill: one family of
        programs carries the state from span to span."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_paged_layers),),
            state=self.state_spec(),
            programs=__name__,
            program_prefix='hybrid_',
            dense_prefill=False,
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'GraniteHybridConfig':
        if hf.get('position_embedding_type', 'nope') != 'nope':
            raise ValueError(
                'granitemoehybrid: only position_embedding_type "nope" is '
                f"implemented, got {hf['position_embedding_type']!r}"
            )
        if hf.get('mamba_n_groups', 1) != 1:
            raise ValueError(
                'granitemoehybrid: served with the published mamba_n_groups 1 (the '
                f"Mamba-2 functions take more: file's end), got {hf['mamba_n_groups']}"
            )
        if hf.get('mamba_proj_bias', False) or hf.get('attention_bias', False):
            raise ValueError('granitemoehybrid: projection biases not implemented')
        d_inner = hf['mamba_n_heads'] * hf['mamba_d_head']
        if d_inner != hf.get('mamba_expand', 2) * hf['hidden_size']:
            raise ValueError(
                'granitemoehybrid: mamba_n_heads * mamba_d_head must equal mamba_expand'
                ' * hidden_size here (a family with another inner width states d_inner)'
            )
        held = hf['num_local_experts']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            layer_types=tuple(hf['layer_types']),
            num_heads=hf['num_attention_heads'],
            num_kv_heads=hf.get('num_key_value_heads', hf['num_attention_heads']),
            mamba_n_heads=hf['mamba_n_heads'],
            mamba_d_head=hf['mamba_d_head'],
            mamba_d_state=hf['mamba_d_state'],
            mamba_d_conv=hf['mamba_d_conv'],
            mamba_chunk_size=hf.get('mamba_chunk_size', 256),
            intermediate_size=hf['intermediate_size'],
            shared_intermediate_size=hf['shared_intermediate_size'],
            # Two keys beside the published ones state a chip's share.
            num_experts=hf.get('num_routed_experts', held),
            num_local_experts=held,
            first_local_expert=hf.get('first_local_expert', 0),
            experts_per_token=hf['num_experts_per_tok'],
            embedding_multiplier=hf['embedding_multiplier'],
            attention_multiplier=hf['attention_multiplier'],
            residual_multiplier=hf['residual_multiplier'],
            logits_scaling=hf['logits_scaling'],
            rms_norm_eps=hf.get('rms_norm_eps', 1e-5),
            max_position_embeddings=hf.get('max_position_embeddings', 131072),
            tie_word_embeddings=hf.get('tie_word_embeddings', True),
        )


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: GraniteHybridConfig, kind: str) -> dict:
    """``name -> shape`` of one layer's parameters (kernels ``[in, out]``)."""
    h, i, s = cfg.hidden_size, cfg.intermediate_size, cfg.shared_intermediate_size
    e = cfg.num_local_experts
    shapes = {
        'ln': (h,), 'mlp_ln': (h,),
        'router': (h, cfg.num_experts),
        'gate': (e, h, i), 'up': (e, h, i), 'down': (e, i, h),
        'shared_gate': (h, s), 'shared_up': (h, s), 'shared_down': (s, h),
    }
    if kind == 'attention':
        q_out = cfg.num_heads * cfg.head_size
        kv_out = cfg.num_kv_heads * cfg.head_size
        shapes.update(q=(h, q_out), k=(h, kv_out), v=(h, kv_out), o=(q_out, h))
    else:
        shapes.update(
            in_proj=(h, cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads),
            conv=(cfg.mamba_d_conv, cfg.conv_dim), conv_bias=(cfg.conv_dim,),
            dt_bias=(cfg.mamba_n_heads,), A_log=(cfg.mamba_n_heads,),
            D=(cfg.mamba_n_heads,), norm=(cfg.d_inner,),
            out_proj=(cfg.d_inner, h),
        )
    return shapes


_SCALES = ('ln', 'mlp_ln', 'norm')  # {'scale': ...} leaves
_VECTORS = ('conv', 'conv_bias', 'dt_bias', 'A_log', 'D')  # bare leaves


def _wrap(name: str, leaf):
    if name in _SCALES:
        return {'scale': leaf}
    if name in _VECTORS:
        return leaf
    return {'kernel': leaf}


def mamba_leaf(name, key, shape, normal):
    """``common.seeded_tree``'s rule for a Mamba-2 mixer's leaves: ``A``
    uniform in [1, 16] and ``dt`` log-uniform in [0.001, 0.1] (the published
    initialisation's ranges; float32), taps and their bias normal(0, 0.5)."""
    if name == 'A_log':
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == 'dt_bias':
        dt = jnp.exp(jax.random.uniform(
            key, shape, F32, np.log(0.001), np.log(0.1)
        ))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    return normal(key, shape, 0.5) if name in ('conv', 'conv_bias') else None


def _trees(cfg: GraniteHybridConfig) -> dict:
    """``kind -> (fold-in number, layers, leaf shapes)``."""
    return {
        'mamba': (1, cfg.num_mamba_layers, _layer_shapes(cfg, 'mamba')),
        'attention': (2, cfg.num_paged_layers, _layer_shapes(cfg, 'attention')),
    }


def init_on_device(rng: jax.Array, cfg: GraniteHybridConfig) -> dict:
    """Random parameters made on the device in ``cfg.dtype``: normal(0,
    0.02) kernels, unit norm scales and ``D``, the mixer's leaves by
    ``mamba_leaf``, one RNG call per parameter kind."""
    return common.seeded_tree(
        rng, cfg.dtype, cfg.hidden_size,
        {'embed': (cfg.vocab_size, cfg.hidden_size)}, _trees(cfg), _wrap,
        (*_SCALES, 'D'), mamba_leaf,
    )


def param_specs(cfg: GraniteHybridConfig, params: dict | None = None) -> dict:
    """Expert banks over ``expert``, everything else replicated."""
    return common.tree_specs(
        {'embed': (cfg.vocab_size, cfg.hidden_size)}, _trees(cfg), _wrap,
        [(kind, name) for kind in ('mamba', 'attention') for name in _BANKS],
    )


def params_from_hf(state: dict[str, np.ndarray], cfg: GraniteHybridConfig) -> dict:
    """Convert HF ``GraniteMoeHybridForCausalLM`` weights. HF stacks the
    experts' ``input_linear`` as ``[E, 2 * I, H]``, gate rows first; a chip
    that holds a share keeps experts ``first_local_expert`` onward."""
    sd = {k.removeprefix('model.'): np.asarray(v) for k, v in state.items()}
    lo = cfg.first_local_expert
    held = slice(lo, lo + cfg.num_local_experts)
    i, s = cfg.intermediate_size, cfg.shared_intermediate_size

    def t(key):  # torch Linear [out, in] -> [in, out]
        return np.ascontiguousarray(sd[key].T)

    def layer(li: int, kind: str) -> dict:
        p = f'layers.{li}'
        moe_in = sd[f'{p}.block_sparse_moe.input_linear.weight'][held]
        shared_in = sd[f'{p}.shared_mlp.input_linear.weight']
        out = {
            'ln': sd[f'{p}.input_layernorm.weight'],
            'mlp_ln': sd[f'{p}.post_attention_layernorm.weight'],
            'router': t(f'{p}.block_sparse_moe.router.layer.weight'),
            'gate': np.ascontiguousarray(moe_in[:, :i].transpose(0, 2, 1)),
            'up': np.ascontiguousarray(moe_in[:, i:].transpose(0, 2, 1)),
            'down': np.ascontiguousarray(
                sd[f'{p}.block_sparse_moe.output_linear.weight'][held]
                .transpose(0, 2, 1)
            ),
            'shared_gate': np.ascontiguousarray(shared_in[:s].T),
            'shared_up': np.ascontiguousarray(shared_in[s:].T),
            'shared_down': t(f'{p}.shared_mlp.output_linear.weight'),
        }
        if kind == 'attention':
            for name in 'qkvo':
                out[name] = t(f'{p}.self_attn.{name}_proj.weight')
        else:
            out.update(
                in_proj=t(f'{p}.mamba.in_proj.weight'),
                # torch depthwise Conv1d weight [C, 1, K] -> [K, C]
                conv=np.ascontiguousarray(sd[f'{p}.mamba.conv1d.weight'][:, 0].T),
                conv_bias=sd[f'{p}.mamba.conv1d.bias'],
                dt_bias=sd[f'{p}.mamba.dt_bias'],
                A_log=sd[f'{p}.mamba.A_log'],
                D=sd[f'{p}.mamba.D'],
                norm=sd[f'{p}.mamba.norm.weight'],
                out_proj=t(f'{p}.mamba.out_proj.weight'),
            )
        return {name: _wrap(name, leaf) for name, leaf in out.items()}

    params = {
        'embed': sd['embed_tokens.weight'][: cfg.vocab_size],
        'final_ln': {'scale': sd['norm.weight']},
    }
    for kind in ('mamba', 'attention'):
        params[kind] = common.stack_layers([
            layer(li, kind)
            for li, lt in enumerate(cfg.layer_types) if lt == kind
        ])
    return params


# ------------------------------------------------------------ shared parts
def _norm(x, scale, cfg):
    return common.rms_norm(x, scale, cfg.rms_norm_eps)


def _embed(params, cfg, input_ids):
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.asarray(params['embed'])[input_ids].astype(dtype)
    return x * jnp.asarray(cfg.embedding_multiplier, dtype)


_BANKS = ('gate', 'up', 'down')


def _mlp(x, lp, cfg, counted, banks, li):
    """``moe(h) + shared(h)`` of one layer for ``x [T, H]`` (already
    normed); returns the sum and the layer's pair counts. ``banks`` is the
    kind's whole tree: the expert banks stay stacked, ``li`` picks the
    layer inside the expert matmuls (``models/moe.py``)."""
    routed, pairs = routed_experts(
        x, lp['router']['kernel'], *(banks[n]['kernel'] for n in _BANKS),
        cfg.experts_per_token, first_expert=cfg.first_local_expert,
        counted=counted, layer=li,
    )
    shared = common.swiglu(
        x, lp['shared_gate']['kernel'], lp['shared_up']['kernel'],
        lp['shared_down']['kernel'],
    )
    return routed + shared, pairs


def _finish_layer(x, mixed, lp, cfg, counted, banks, li):
    """Residual of the mixer's output, then the MLP block."""
    res = jnp.asarray(cfg.residual_multiplier, x.dtype)
    x = x + mixed * res
    normed = _norm(x, lp['mlp_ln']['scale'], cfg)
    flat = normed.reshape(-1, normed.shape[-1])
    mlp, pairs = _mlp(flat, lp, cfg, counted.reshape(-1), banks, li)
    return x + mlp.reshape(x.shape) * res, pairs


def logits(params: dict, cfg: GraniteHybridConfig, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """``hidden`` is already final-normed; tied head over the held rows of
    the embedding, divided by ``logits_scaling``."""
    out = common.dense(hidden, jnp.asarray(params['embed']).T).astype(F32)
    return out / cfg.logits_scaling


# ------------------------------------------------------------------ Mamba-2
def ssd_chunked(x, dt, a, b_in, c_in, ssm0, chunk: int):  # distlint: traced
    """The Mamba-2 recurrence over a span, evaluated ``chunk`` steps at a
    time (the SSD form): inside a chunk as one masked matrix product, from
    chunk to chunk through the carried state. ``x [B, S, H, P]``, ``dt [B,
    S, H]`` (0 where a position does not count: the state passes through),
    ``a [H]`` negative, ``b_in``/``c_in [B, S, N]`` (one group) or ``[B, S, G, N]``
    (head ``i`` reads group ``i // (H / G)``), ``ssm0 [B, H, P, N]``. Returns ``y [B, S,
    H, P]`` (no ``D`` skip) and the last state, float32, the same at any ``chunk``."""
    bsz, s, h, p = x.shape
    pad = -s % chunk
    if pad:
        x, dt, b_in, c_in = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_in, c_in)
        )
    n_chunks = (s + pad) // chunk

    def split(t):  # [B, S, ...] -> [C, B, Q, ...]
        return jnp.moveaxis(
            t.reshape(bsz, n_chunks, chunk, *t.shape[2:]), 1, 0
        ).astype(F32)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one_chunk(ssm, xs):
        x_c, dt_c, b_c, c_c = xs  # [B, Q, H, P], [B, Q, H], [B, Q, N] x2
        cum = jnp.cumsum(dt_c * a, axis=1)  # [B, Q, H], decreasing
        # From the state the chunk starts with.
        y = jnp.einsum('bqn,bhpn->bqhp', c_c, ssm) * jnp.exp(cum)[..., None]
        # Inside the chunk: position i sees j <= i, decayed by cum_i - cum_j.
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # [B, Qi, Qj, H]
        decay = jnp.exp(jnp.where(lower[None, :, :, None], diff, -jnp.inf))
        scores = jnp.einsum('bin,bjn->bij', c_c, b_c)  # [B, Qi, Qj]
        w = scores[..., None] * decay * dt_c[:, None, :, :]  # [B, Qi, Qj, H]
        y = y + jnp.einsum('bijh,bjhp->bihp', w, x_c)
        # The state the next chunk starts with.
        tail = jnp.exp(cum[:, -1:, :] - cum) * dt_c  # [B, Q, H]
        ssm = ssm * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            'bqh,bqhp,bqn->bhpn', tail, x_c, b_c
        )
        return ssm, y

    step = one_chunk if b_in.ndim == 3 else _one_chunk_grouped(a, lower)
    ssm, y = jax.lax.scan(
        step, ssm0.astype(F32), tuple(split(t) for t in (x, dt, b_in, c_in))
    )
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s + pad, h, p)
    return y[:, :s], ssm


def _mamba_inputs(lp, cfg, conv_window):
    """The convolution of a Mamba mixer and its split. ``conv_window`` is
    the convolution's input, ``[..., K - 1 + S, conv_dim]``: the carried
    columns, then the span's own. Returns ``x [..., S, heads, P]``, ``B``
    and ``C [..., S, N]`` (``[..., S, G, N]`` with several groups), float32."""
    k = cfg.mamba_d_conv
    s = conv_window.shape[-2] - (k - 1)
    w = lp['conv'].astype(F32)
    win = conv_window.astype(F32)
    conv = sum(
        w[j] * jax.lax.slice_in_dim(win, j, j + s, axis=-2) for j in range(k)
    ) + lp['conv_bias'].astype(F32)
    xbc = jax.nn.silu(conv)
    di = cfg.d_inner
    x = xbc[..., :di].reshape(*xbc.shape[:-1], cfg.mamba_n_heads, cfg.mamba_d_head)
    return (x, *_b_and_c(xbc, cfg))


def _mamba_out(y, z, lp, cfg, dtype):
    """Gate first, then the norm over each group's channels apart (all of
    ``d_inner`` with one group), then the output projection."""
    gated = y.reshape(*y.shape[:-2], cfg.d_inner) * jax.nn.silu(z.astype(F32))
    normed = _gated_norm(gated, lp['norm']['scale'], cfg)
    return common.dense(normed.astype(dtype), lp['out_proj']['kernel'])


def _split_in_proj(h, lp, cfg):
    proj = _scale_parts(common.dense(h, lp['in_proj']['kernel']), cfg)
    di, cd = cfg.d_inner, cfg.conv_dim
    return proj[..., :di], proj[..., di:di + cd], proj[..., di + cd:]


def _dt_a(dt_raw, lp):
    dt = jax.nn.softplus(dt_raw.astype(F32) + lp['dt_bias'].astype(F32))
    return dt, -jnp.exp(lp['A_log'].astype(F32))


def mamba_span(h, lp, cfg, ssm0, conv0, tail_lens):  # distlint: traced
    """A Mamba-2 mixer over a span ``h [B, S, H]`` that starts from state
    (``ssm0 [B, heads, P, N]`` float32, ``conv0 [B, K - 1, conv_dim]``) and
    counts the first ``tail_lens [B]`` positions of each row. Returns the
    output and the state after each row's last counted position."""
    with jax.named_scope('distllm.ssm_prefill'):
        s = h.shape[1]
        z, xbc, dt_raw = _split_in_proj(h, lp, cfg)
        window = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)
        x, b_in, c_in = _mamba_inputs(lp, cfg, window)
        dt, a = _dt_a(dt_raw, lp)
        valid = jnp.arange(s)[None, :] < tail_lens[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
        y, ssm = ssd_chunked(x, dt, a, b_in, c_in, ssm0, cfg.mamba_chunk_size)
        y = y + lp['D'].astype(F32)[:, None] * x
        # The last K - 1 counted columns: carried ones where the row is short
        # (the one gather every family with a convolution's tail makes).
        conv = common.conv_tail(window, tail_lens, cfg.mamba_d_conv - 1)
        return _mamba_out(y, z, lp, cfg, h.dtype), ssm, conv.astype(conv0.dtype)


def mamba_step(h, lp, cfg, ssm0, conv0, live):  # distlint: traced
    """One step of the recurrence for ``h [B, H]``; rows that are not
    ``live`` keep their state."""
    with jax.named_scope('distllm.ssm_decode'):
        z, xbc, dt_raw = _split_in_proj(h, lp, cfg)
        window = jnp.concatenate(
            [conv0.astype(xbc.dtype), xbc[:, None]], axis=1
        )  # [B, K, conv_dim]
        x, b_in, c_in = _mamba_inputs(lp, cfg, window)
        x, b_in, c_in = x[:, 0], b_in[:, 0], c_in[:, 0]
        dt, a = _dt_a(dt_raw, lp)  # [B, heads]
        ssm = (
            ssm0 * jnp.exp(dt * a)[..., None, None]
            + (dt[..., None] * x)[..., None] * _of_heads(b_in, cfg)
        )
        y = jnp.sum(ssm * _of_heads(c_in, cfg), axis=-1)
        y = y + lp['D'].astype(F32)[:, None] * x
        out = _mamba_out(y, z, lp, cfg, h.dtype)
        ssm = jnp.where(live[:, None, None, None], ssm, ssm0).astype(ssm0.dtype)
        conv = jnp.where(live[:, None, None], window[:, 1:].astype(conv0.dtype), conv0)
        return out, ssm, conv


# ---------------------------------------------------------------- attention
def _qkv(normed, lp, cfg):
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_size)  # noqa: E731
    return (
        heads(common.dense(normed, lp['q']['kernel']), cfg.num_heads),
        heads(common.dense(normed, lp['k']['kernel']), cfg.num_kv_heads),
        heads(common.dense(normed, lp['v']['kernel']), cfg.num_kv_heads),
    )


def _attn_out(attn, lp, cfg):
    return common.dense(
        attn.reshape(*attn.shape[:-2], cfg.num_heads * cfg.head_size),
        lp['o']['kernel'],
    )


# ----------------------------------------------------------------- forwards
def _run_indices(first, count):
    return jnp.arange(first, first + count, dtype=jnp.int32)


def _gather_state(state, name, first, count, slots):
    """Rows ``slots`` of layers ``first..first+count`` of the state pool,
    stacked ``[count, B, ...]`` for a layer scan."""
    return jnp.stack([state[name][i][slots] for i in range(first, first + count)])


def _scatter_state(state, name, first, rows, slots):
    """Write ``rows [count, B, ...]`` back; a pad row's slot lies past the
    pool and its write is dropped."""
    leaves = list(state[name])
    for j in range(rows.shape[0]):
        leaves[first + j] = leaves[first + j].at[slots].set(
            rows[j].astype(leaves[first + j].dtype), mode='drop'
        )
    return {**state, name: tuple(leaves)}


def apply(  # distlint: traced
    params: dict,
    cfg: GraniteHybridConfig,
    input_ids: jnp.ndarray,  # [B, S], right-padded
    attention_mask: jnp.ndarray,  # [B, S]
) -> jnp.ndarray:
    """Dense causal forward from zero state: ``[B, S]`` -> final-normed
    hidden states ``[B, S, H]``."""
    return prefill(params, cfg, input_ids, attention_mask)[0]


def prefill(  # distlint: traced
    params: dict,
    cfg: GraniteHybridConfig,
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray,
):
    """Dense forward that also returns what a sequence holds afterwards:
    ``(hidden, k [L_attn, B, S, N_kv, Hd], v, state)`` with ``state`` the
    ``state_spec`` tree with a leading ``[B]`` on every leaf."""
    b, s = input_ids.shape
    tail_lens = attention_mask.astype(jnp.int32).sum(axis=1)
    valid = attention_mask.astype(bool)
    mask = common.causal_mask(s, s)[None, None] & valid[:, None, None, :]
    x = _embed(params, cfg, input_ids)
    spec = cfg.state_spec()
    ks, vs, ssms, convs = [], [], [], []

    def mamba_layer(x, li):
        lp = common.layer_at(params['mamba'], li, skip=_BANKS, dynamic=True)
        ssm0 = jnp.zeros((b, *spec['ssm'][0].shape), F32)
        conv0 = jnp.zeros((b, *spec['conv'][0].shape), spec['conv'][0].dtype)
        mixed, ssm, conv = mamba_span(
            _norm(x, lp['ln']['scale'], cfg), lp, cfg, ssm0, conv0, tail_lens
        )
        x, _ = _finish_layer(x, mixed, lp, cfg, valid, params['mamba'], li)
        return x, (ssm, conv)

    def attn_layer(x, li):
        lp = common.layer_at(params['attention'], li, skip=_BANKS, dynamic=True)
        q, k, v = _qkv(_norm(x, lp['ln']['scale'], cfg), lp, cfg)
        attn = common.sdpa(q, k, v, mask=mask, scale=cfg.attention_multiplier)
        x, _ = _finish_layer(
            x, _attn_out(attn, lp, cfg), lp, cfg, valid, params['attention'], li
        )
        return x, (k, v)

    for kind, first, count in cfg.layer_runs():
        run = _run_indices(first, count)
        if kind == 'mamba':
            x, (ssm, conv) = jax.lax.scan(mamba_layer, x, run)
            ssms.extend(ssm)
            convs.extend(conv)
        else:
            x, (k, v) = jax.lax.scan(attn_layer, x, run)
            ks.append(k)
            vs.append(v)
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    state = {'ssm': tuple(ssms), 'conv': tuple(convs)}
    return hidden, jnp.concatenate(ks), jnp.concatenate(vs), state


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: GraniteHybridConfig,
    input_ids: jnp.ndarray,  # [B, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache: jnp.ndarray,  # [L_attn, num_blocks, block_size, N_kv * Hd]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    state: dict,  # the state pool: per Mamba layer [slots, ...]
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
    x = _embed(params, cfg, input_ids)

    def mamba_layer(x, xs):
        li, ssm0, conv0 = xs
        lp = common.layer_at(params['mamba'], li, skip=_BANKS, dynamic=True)
        mixed, ssm, conv = mamba_span(
            _norm(x, lp['ln']['scale'], cfg), lp, cfg, ssm0, conv0, tail_lens
        )
        x, _ = _finish_layer(x, mixed, lp, cfg, valid, params['mamba'], li)
        return x, (ssm, conv)

    def attn_layer(carry, xs):
        x, k_cache, v_cache = carry
        li = xs
        lp = common.layer_at(params['attention'], li, skip=_BANKS, dynamic=True)
        q, k, v = _qkv(_norm(x, lp['ln']['scale'], cfg), lp, cfg)
        # the stacked pools whole, with the layer whose pages are meant
        k_cache, v_cache = write_chunk_kv(
            k_cache, v_cache, k, v, block_tables, positions, valid, layer=li
        )
        attn = ragged_paged_attention(
            q, k_cache, v_cache, block_tables, context_lens, positions,
            q_lens=tail_lens, scale=cfg.attention_multiplier,
            backend=attn_backend, layer=li,
        )
        x, _ = _finish_layer(
            x, _attn_out(attn, lp, cfg), lp, cfg, valid, params['attention'], li
        )
        return (x, k_cache, v_cache), None

    for kind, first, count in cfg.layer_runs():
        run = _run_indices(first, count)
        if kind == 'mamba':
            ssm0 = _gather_state(state, 'ssm', first, count, slots)
            conv0 = _gather_state(state, 'conv', first, count, slots)
            ssm0 = jnp.where(fresh[None, :, None, None, None], 0.0, ssm0)
            conv0 = jnp.where(fresh[None, :, None, None], 0, conv0)
            x, (ssm, conv) = jax.lax.scan(mamba_layer, x, (run, ssm0, conv0))
            state = _scatter_state(state, 'ssm', first, ssm, slots)
            state = _scatter_state(state, 'conv', first, conv, slots)
        elif count == 1:
            # No loop for a run of one, which is all the published pattern
            # has: the layer's weights are a static slice of their stacks.
            # (The K/V pool is addressed by layer either way, never sliced.)
            (x, k_cache, v_cache), _ = attn_layer((x, k_cache, v_cache), first)
        else:
            (x, k_cache, v_cache), _ = jax.lax.scan(
                attn_layer, (x, k_cache, v_cache), run
            )
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    last_hidden = common.last_token(hidden, tail_lens)
    return logits(params, cfg, last_hidden)[:, 0], k_cache, v_cache, state


def _decode_core(
    params, cfg, attn_backend, input_ids, positions, context_lens, caches,
    block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first three arguments are bound; ``caches`` is ``(k_cache, v_cache,
    state)``). The layers are walked unrolled: each Mamba
    layer's state is a buffer of its own, rewritten whole and in place, and
    a static slice of the stacked kernels folds into its matmul."""
    from distllm_tpu.ops.paged_attention import decode_attention, write_token_kv

    k_cache, v_cache, state = caches
    x = _embed(params, cfg, input_ids)  # [B, H]
    ssms, convs = list(state['ssm']), list(state['conv'])
    pairs = jnp.zeros((2,), jnp.int32)
    seen = {'mamba': 0, 'attention': 0}
    for kind in cfg.layer_types:
        i = seen[kind]
        seen[kind] += 1
        lp = common.layer_at(params[kind], i, skip=_BANKS)
        normed = _norm(x, lp['ln']['scale'], cfg)
        if kind == 'mamba':
            mixed, ssms[i], convs[i] = mamba_step(
                normed, lp, cfg, ssms[i], convs[i], live
            )
        else:
            q, k, v = _qkv(normed, lp, cfg)
            k_cache, v_cache = write_token_kv(
                k_cache, v_cache, k, v, block_tables, positions, layer=i
            )
            attn = decode_attention(
                q, k_cache, v_cache, block_tables, context_lens, positions,
                backend=attn_backend, scale=cfg.attention_multiplier, layer=i,
            )
            mixed = _attn_out(attn, lp, cfg)
        x, layer_pairs = _finish_layer(
            x, mixed, lp, cfg, live, params[kind], i
        )
        pairs = pairs + layer_pairs
    hidden = _norm(x, params['final_ln']['scale'], cfg)
    state = {'ssm': tuple(ssms), 'conv': tuple(convs)}
    return logits(params, cfg, hidden), (k_cache, v_cache, state), pairs


def decode_loop(  # distlint: traced
    params: dict,
    cfg: GraniteHybridConfig,
    input_ids: jnp.ndarray,  # [B] last emitted token per slot
    positions: jnp.ndarray,  # [B]
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
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
    del max_table_positions  # no rotation: no table of positions
    tokens, (k_cache, v_cache, state), ids, pairs = common.decode_window(
        functools.partial(_decode_core, params, cfg, attn_backend),
        input_ids, positions, context_lens, (k_cache, v_cache, state),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=jnp.zeros((2,), jnp.int32),
    )
    return tokens, k_cache, v_cache, ids, state, pairs


# ------------------------------------------- Mamba-2 beyond this family's sizes
# ``ssd_chunked``, ``mamba_span``, ``mamba_step`` and their parts serve every
# family with a Mamba-2 mixer (``models/falcon_h1.py`` imports them) and take
# their sizes from the config they are given: ``mamba_n_heads``,
# ``mamba_d_head`` and ``d_inner`` (their product here; a family whose inner
# width is its own states it), ``mamba_d_state``, ``mamba_d_conv``,
# ``mamba_chunk_size``, ``conv_dim`` (``d_inner + 2 * groups * state``) and, where
# the config names them, ``mamba_n_groups`` (``B`` and ``C`` come a group; head
# ``i`` reads group ``i // (heads / groups)``, and the gated norm is taken over
# each group's channels apart) and ``ssm_multipliers`` (five factors for the
# in-projection's parts ``z, x, B, C, dt``, applied before the convolution). A
# config that names neither has one group and no factors, and then each function
# traces the very operations it traced before it learnt the rest
# (tests/test_falcon_h1.py pins this family's lowered text): the group count is
# static and each part branches on it.
def _groups(cfg) -> int:
    return getattr(cfg, 'mamba_n_groups', 1)


def _b_and_c(xbc, cfg):
    """``B`` and ``C`` of the convolved ``[x | B | C]``: ``[..., N]`` each with
    one group, ``[..., G, N]`` with several."""
    di, n, g = cfg.d_inner, cfg.mamba_d_state, _groups(cfg)
    if g == 1:
        return xbc[..., di:di + n], xbc[..., di + n:]
    by_group = lambda t: t.reshape(*t.shape[:-1], g, n)  # noqa: E731
    return by_group(xbc[..., di:di + g * n]), by_group(xbc[..., di + g * n:])


def _gated_norm(gated, scale, cfg):
    """RMS norm of ``gated [..., d_inner]`` over each group's channels apart."""
    g = _groups(cfg)
    if g == 1:
        return common.rms_norm(gated, scale, cfg.rms_norm_eps)
    return common.rms_norm(
        gated.reshape(*gated.shape[:-1], g, cfg.d_inner // g),
        scale.reshape(g, cfg.d_inner // g), cfg.rms_norm_eps,
    ).reshape(gated.shape)


def _scale_parts(proj, cfg):
    """The in-projection's five parts ``z, x, B, C, dt`` times their factors."""
    factors = getattr(cfg, 'ssm_multipliers', None)
    if factors is None:
        return proj
    gn = _groups(cfg) * cfg.mamba_d_state
    widths = (cfg.d_inner, cfg.d_inner, gn, gn, cfg.mamba_n_heads)
    return proj * jnp.asarray(
        np.repeat(np.asarray(factors, np.float32), widths), proj.dtype
    )


def _of_heads(t, cfg):
    """A step's ``B`` or ``C`` against the state ``[B, heads, P, N]``: ``t [B,
    N]`` for every head, or ``t [B, G, N]`` as the row of each head's group."""
    if _groups(cfg) == 1:
        return t[:, None, None, :]
    return jnp.repeat(t, cfg.mamba_n_heads // _groups(cfg), axis=1)[:, :, None, :]


def _one_chunk_grouped(a, lower):
    """``ssd_chunked``'s chunk step with a score ``[Qi, Qj]`` a group; the heads
    of a group lie together: ``[B, Q, H, P]`` is ``[B, Q, G, H / G, P]``."""

    def one_chunk(ssm, xs):
        x_c, dt_c, b_c, c_c = xs  # [B, Q, H, P], [B, Q, H], [B, Q, G, N] x2
        (bsz, chunk, h, p), g = x_c.shape, b_c.shape[2]

        def by_group(t):  # [B, Q, H, ...] -> [B, Q, G, H / G, ...]
            return t.reshape(*t.shape[:2], g, h // g, *t.shape[3:])

        cum = jnp.cumsum(dt_c * a, axis=1)  # [B, Q, H]
        y = jnp.einsum(
            'bqgn,bghpn->bqghp', c_c, ssm.reshape(bsz, g, h // g, p, -1)
        ).reshape(x_c.shape) * jnp.exp(cum)[..., None]
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # [B, Qi, Qj, H]
        decay = jnp.exp(jnp.where(lower[None, :, :, None], diff, -jnp.inf))
        scores = jnp.einsum('bign,bjgn->bijg', c_c, b_c)  # [B, Qi, Qj, G]
        w = (decay * dt_c[:, None, :, :]).reshape(
            bsz, chunk, chunk, g, h // g
        ) * scores[..., None]
        y = y + jnp.einsum('bijgh,bjghp->bighp', w, by_group(x_c)).reshape(
            x_c.shape
        )
        tail = jnp.exp(cum[:, -1:, :] - cum) * dt_c  # [B, Q, H]
        ssm = ssm * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            'bqgh,bqghp,bqgn->bghpn', by_group(tail), by_group(x_c), b_c
        ).reshape(ssm.shape)
        return ssm, y

    return one_chunk
