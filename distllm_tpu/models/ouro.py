"""Ouro looped decoders (``model_type ouro``: ByteDance Ouro-1.4B / 2.6B,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741):
ONE stack of sandwich-normed full-attention layers that a token runs
``total_ut_steps`` times over the same weights, the model's one final norm
closing every pass and feeding the next, and a learned gate that chooses
which pass's hidden state the head reads.

The layer is ``models/mistral.py``'s with ``post_norms`` (its four norms a
layer), called through the functions both families share
(``mistral._span_layer``, ``mistral._token_layer``, ``mistral._dense_layers``)
with the weights' index and the cache's plane APART: layer ``l`` of pass ``t``
reads weights ``l`` and writes and reads plane ``t * L + l`` of one stacked
K/V pool of ``L * total_ut_steps`` planes under one block table
(``cache_spec()``). A pass never reads another pass's plane. With ``x_0 =
E[ids]``, for ``t = 0 .. T - 1``::

    for l = 0 .. L - 1 (weights l, plane t L + l):
        u = rms(x; input_layernorm)
        q, k, v = W_q u, W_k u, W_v u; rope(q), rope(k) at the token's position
        k, v -> plane t L + l; a = causal softmax(q k^T / sqrt(d)) v over it
        h = x + rms(W_o a; input_layernorm_2)
        x = h + rms(W_down(silu(W_gate m) * W_up m); post_attention_layernorm_2)
            with m = rms(h; post_attention_layernorm)
    z_t = rms(x; norm);  g_t = w_g . z_t + b_g (float32);  x <- z_t

    lambda_t = sigmoid(g_t); p_t = lambda_t prod_{j<t}(1 - lambda_j), the
    last pass takes the remainder; the head reads z_e, e the first t whose
    cumulative p reaches ``early_exit_threshold``, else T - 1.

Every pass is computed whatever ``e`` is, as the published code does: the
gate chooses a hidden state, it saves no work. Skipping a token's passes
after its exit, or sharing one plane between the passes at decode, would be
approximations of the published forward pass and are not here.

Programs: prefill is the rolled layer scan inside a rolled loop over the
passes; the decode window is ``common.decode_window`` around a core whose
layers are unrolled (static weight slices, as ``mistral._decode_core``)
inside a ROLLED loop over the passes, the pools in its carry and the plane
a traced ``t * L + l``: unrolled over ``T * L`` bodies the window would be
``T`` times the program for the same text.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import common, mistral
from distllm_tpu.models.mistral import MistralConfig

F32 = jnp.float32


class OuroConfig(MistralConfig):
    name: Literal['ouro'] = 'ouro'  # type: ignore[assignment]
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int | None = 128
    intermediate_size: int = 5632
    max_position_embeddings: int = 65536
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    post_norms: bool = True  # the layer's four norms: this family's layer
    total_ut_steps: int = 4  # passes of the stack a token
    early_exit_threshold: float = 1.0

    @property
    def num_planes(self) -> int:
        """K/V planes a token holds: one a layer a pass."""
        return self.num_layers * self.total_ut_steps

    def cache_spec(self) -> common.CacheSpec:
        """One full-context K/V group of ``num_layers * total_ut_steps``
        planes under one block table, this module's programs and no dense
        prefill (every prefill goes through the planes)."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_planes),),
            programs=__name__,
            program_prefix='ouro_',
            dense_prefill=False,
            passes=self.total_ut_steps,
        )

    def prefill_forms(self, programs: dict) -> dict:
        """A telemetry key of the family's own (the engine writes it once):
        the form the decode window took, the passes a rolled loop and the
        layers inside it unrolled."""
        return {'loop_window_form': 'passes_rolled_layers_unrolled'}

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'OuroConfig':
        """The published keys as they are; values this module does not
        implement are refused by name."""
        layer_types = hf.get('layer_types') or []
        refusals = (
            ('use_sliding_window', bool(hf.get('use_sliding_window', False)),
             'a sliding window'),
            ('sliding_window', hf.get('sliding_window') is not None,
             'a sliding window'),
            ('layer_types', any(t != 'full_attention' for t in layer_types),
             'a layer that is not full attention'),
            ('rope_scaling', hf.get('rope_scaling') is not None,
             'a scaled rotation'),
            ('total_ut_steps', int(hf.get('total_ut_steps', 4)) < 1,
             'a stack that never runs'),
            ('tie_word_embeddings', bool(hf.get('tie_word_embeddings', False)),
             'a head tied to the embedding'),
            ('attention_bias', bool(hf.get('attention_bias', False)),
             'projection biases'),
            ('hidden_act', hf.get('hidden_act', 'silu') != 'silu',
             'another activation than silu'),
        )
        for key, refused, what in refusals:
            if refused:
                raise ValueError(
                    f'ouro: {key}={hf.get(key)!r} is not implemented ({what})'
                )
        heads = hf['num_attention_heads']
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_layers=hf['num_hidden_layers'],
            num_heads=heads,
            num_kv_heads=hf.get('num_key_value_heads', heads),
            head_dim=hf.get('head_dim') or hf['hidden_size'] // heads,
            intermediate_size=hf['intermediate_size'],
            max_position_embeddings=hf.get('max_position_embeddings', 65536),
            rope_theta=float(hf.get('rope_theta', 1e6)),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-6),
            total_ut_steps=int(hf.get('total_ut_steps', 4)),
            early_exit_threshold=float(hf.get('early_exit_threshold', 1.0)),
        )


# ------------------------------------------------------------- parameters
def _with_gate(params: dict, kernel) -> dict:
    """``mistral``'s tree (the stack with its four norms a layer, the
    embedding, the final norm, the head) and the exit gate beside it."""
    bias = jnp.zeros((1,), kernel.dtype)
    return {**params, 'exit_gate': {'kernel': kernel, 'bias': bias}}


def init(rng: jax.Array, cfg: OuroConfig) -> dict:
    """Float32 numpy parameters of test size: ``mistral.init``'s, and the
    gate drawn like every kernel (normal 0.02), its bias 0."""
    gate = jax.random.normal(jax.random.fold_in(rng, 3), (cfg.hidden_size, 1)) * 0.02
    params = _with_gate(mistral.init(rng, cfg), np.asarray(gate, np.float32))
    return jax.tree.map(np.asarray, params)


def init_on_device(rng: jax.Array, cfg: OuroConfig) -> dict:
    """Random parameters made on the device in ``cfg.dtype``:
    ``mistral.init_on_device``'s tree (normal 0.02 kernels, unit norm
    scales) and the gate (normal 0.02, bias 0)."""
    gate = jax.random.normal(
        jax.random.fold_in(rng, 12), (cfg.hidden_size, 1), F32
    ) * 0.02
    return _with_gate(
        mistral.init_on_device(rng, cfg), gate.astype(jnp.dtype(cfg.dtype))
    )


def param_specs(cfg: OuroConfig, params: dict | None = None) -> dict:
    """``mistral.param_specs`` and the gate replicated (the engine refuses
    a mesh for a looped model: the specs are for the tree's shape alone)."""
    from jax.sharding import PartitionSpec as P

    specs = mistral.param_specs(cfg, params)
    return {**specs, 'exit_gate': {'kernel': P(None, None), 'bias': P(None)}}


def params_from_hf(state: dict, cfg: OuroConfig) -> dict:
    """Convert ``OuroForCausalLM`` weights by the published tensor names:
    ``mistral.params_from_hf``'s tree, the layer's four norms
    (``input_layernorm`` before attention, ``input_layernorm_2`` behind it,
    ``post_attention_layernorm`` before the MLP, ``post_attention_layernorm_2``
    behind it) and ``model.early_exit_gate``. A name that is missing is
    refused by name: no checkpoint was at hand where this was written."""
    sd = {k.removeprefix('model.'): v for k, v in state.items()}
    wanted = [
        f'layers.{i}.{norm}.weight' for i in range(cfg.num_layers)
        for norm in ('input_layernorm_2', 'post_attention_layernorm_2')
    ] + ['early_exit_gate.weight', 'lm_head.weight']
    missing = [k for k in wanted if k not in sd and k not in state]
    if missing:
        raise ValueError(
            f'ouro: the checkpoint has no {missing[0]!r} ({len(missing)} '
            'tensors missing): this converter knows the published names '
            'input_layernorm_2, post_attention_layernorm_2 and '
            'model.early_exit_gate alone'
        )
    params = mistral.params_from_hf(state, cfg)

    def stacked(norm):
        return np.stack(
            [sd[f'layers.{i}.{norm}.weight'] for i in range(cfg.num_layers)]
        )

    params['layers']['post_attn_ln'] = {'scale': stacked('input_layernorm_2')}
    params['layers']['post_mlp_ln'] = {
        'scale': stacked('post_attention_layernorm_2')
    }
    gate = np.asarray(sd['early_exit_gate.weight'])  # torch Linear [1, H]
    bias = sd.get('early_exit_gate.bias')
    params['exit_gate'] = {
        'kernel': np.ascontiguousarray(gate.T),
        'bias': np.zeros((1,), gate.dtype) if bias is None else np.asarray(bias),
    }
    return params


# ------------------------------------------------------------ the exit gate
def _exit_start(like: jnp.ndarray):
    """The exit rule's state before the first pass, for tokens ``like [...,
    H]``: ``(chosen z, done, prod(1 - lambda), cumulative p, exit pass)``."""
    rows = like.shape[:-1]
    return (
        jnp.zeros_like(like), jnp.zeros(rows, bool), jnp.ones(rows, F32),
        jnp.zeros(rows, F32), jnp.zeros(rows, jnp.int32),
    )


def _exit_step(params, cfg: OuroConfig, t, z, state):  # distlint: traced
    """Pass ``t``'s normed output ``z [..., H]`` through the gate, in
    float32: ``lambda = sigmoid(w_g . z + b_g)``, ``p_t = lambda prod_{j<t}(1
    - lambda_j)`` (the last pass takes what is left), and a token whose
    cumulative ``p`` first reaches the threshold here, or that reaches the
    last pass, reads this ``z``. Returns the state and the gate's value."""
    chosen, done, survive, cum, exit_pass = state
    gate = params['exit_gate']
    # elementwise and a sum: a float32 matmul at the TPU's default
    # precision would round ``z`` and the gate to bfloat16
    g = jnp.sum(
        z.astype(F32) * jnp.asarray(gate['kernel']).astype(F32)[:, 0], axis=-1
    ) + jnp.asarray(gate['bias']).astype(F32)[0]
    lam = jax.nn.sigmoid(g)
    last = t == cfg.total_ut_steps - 1
    cum = cum + jnp.where(last, survive, lam * survive)
    hit = ~done & ((cum >= cfg.early_exit_threshold) | last)
    chosen = jnp.where(hit[..., None], z, chosen)
    exit_pass = jnp.where(hit, t, exit_pass)
    return (chosen, done | hit, survive * (1.0 - lam), cum, exit_pass), g


def _close_pass(params, cfg: OuroConfig, t, x, state, pick=None):  # distlint: traced
    """What ends a pass: the final norm of ``x`` (what the next pass starts
    from) and the exit rule over the tokens ``pick`` takes of it (all of
    them where ``pick`` is None). Returns ``(z, state, gate)``."""
    with jax.named_scope('distllm.loop_exit'):
        z = mistral._norm(x, params['final_ln']['scale'], cfg)
        state, g = _exit_step(
            params, cfg, t, z if pick is None else pick(z), state
        )
    return z, state, g


def logits(params: dict, cfg: OuroConfig, hidden: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """The head over a hidden state the exit rule chose (already normed)."""
    with jax.named_scope('distllm.head'):
        return mistral.logits(params, cfg, hidden)


# ----------------------------------------------------------------- forwards
def apply(  # distlint: traced
    params: dict,
    cfg: OuroConfig,
    input_ids: jnp.ndarray,  # [B, S], right-padded
    attention_mask: jnp.ndarray,  # [B, S]
    *,
    return_passes: bool = False,
):
    """Dense causal forward with no cache: ``[B, S]`` -> the hidden state
    the exit rule chose of every token ``[B, S, H]`` (what :func:`logits`
    reads). ``return_passes`` adds ``{'z': [T, B, S, H], 'gate': [T, B, S],
    'exit_pass': [B, S]}``: every pass's normed output, its gate's value
    and the pass each token's head reads."""
    rope = mistral._rope_tables(cfg, input_ids.shape[1])
    x = mistral._embed_tokens(params, cfg, input_ids)

    def one_pass(carry, t):
        x, state = carry
        x, _ = mistral._dense_layers(
            params, cfg, rope, x, attention_mask, collect_kv=False
        )
        z, state, g = _close_pass(params, cfg, t, x, state)
        return (z, state), (z, g) if return_passes else None

    (_, state), passes = jax.lax.scan(
        one_pass, (x, _exit_start(x)),
        jnp.arange(cfg.total_ut_steps, dtype=jnp.int32),
    )
    if not return_passes:
        return state[0]
    return state[0], {'z': passes[0], 'gate': passes[1], 'exit_pass': state[4]}


def prefill_paged(  # distlint: traced
    params: dict,
    cfg: OuroConfig,
    input_ids: jnp.ndarray,  # [B, S] tokens of the span (padded)
    positions: jnp.ndarray,  # [B, S] absolute positions
    k_cache: jnp.ndarray,  # [T * L, num_blocks, block_size, N_kv * Hd]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    context_lens: jnp.ndarray,  # [B] valid tokens incl. this span
    tail_lens: jnp.ndarray,  # [B] valid tokens in input_ids (0 = pad row)
    max_table_positions: int | None = None,
    attn_backend: str = 'xla',
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One span of every row through the planes: ``mistral.prefill_paged``'s
    rolled layer scan inside a rolled loop over the passes, layer ``l`` of
    pass ``t`` writing the span's K/V into plane ``t * L + l`` and attending
    over that plane alone. The exit rule runs on each row's last counted
    token, all the head reads. Returns ``(last_logits [B, V] float32,
    k_cache, v_cache)``."""
    s = input_ids.shape[1]
    rope = mistral._rope_tables(
        cfg, max_table_positions or cfg.max_position_embeddings
    )
    valid = jnp.arange(s)[None, :] < tail_lens[:, None]
    span = (positions, valid, block_tables, context_lens, tail_lens)
    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    x = mistral._embed_tokens(params, cfg, input_ids)  # [B, S, H]

    def one_pass(t, carry):
        x, k_cache, v_cache, state = carry

        def layer(carry, xs):
            lp, li = xs
            return mistral._span_layer(
                cfg, rope, attn_backend, span, carry, lp,
                t * cfg.num_layers + li, None, scope=jax.named_scope,
            ), None

        (x, k_cache, v_cache), _ = jax.lax.scan(
            layer, (x, k_cache, v_cache), (params['layers'], layers)
        )
        z, state, _ = _close_pass(
            params, cfg, t, x, state,
            pick=lambda z: common.last_token(z, tail_lens),
        )
        return z, k_cache, v_cache, state

    _, k_cache, v_cache, state = jax.lax.fori_loop(
        0, cfg.total_ut_steps, one_pass,
        (x, k_cache, v_cache, _exit_start(x[:, :1])),
    )
    return logits(params, cfg, state[0])[:, 0], k_cache, v_cache


def _decode_core(
    params, cfg, rope, attn_backend, input_ids, positions, context_lens,
    caches, block_tables, live,
):
    """One token of every row (``common.decode_window``'s ``core`` once its
    first four arguments are bound; ``caches`` is ``(k_cache, v_cache)``).
    The layers are walked unrolled, as ``mistral._decode_core`` walks them
    and for its reasons (a static slice of the stacked kernels folds into
    its matmul), inside a ROLLED loop over the passes: both pools ride its
    carry, every layer scatters the token's row into plane ``t * L + l`` in
    place and the kernel reads that plane of the whole pool, so that no op
    has a pool-sized result but the scatters (``tests/test_aot_tpu.py``).
    The counts it returns are the live rows whose head read each pass,
    ``[T]``."""
    k_cache, v_cache = caches
    row = (positions, block_tables, context_lens)
    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    x = mistral._embed_tokens(params, cfg, input_ids)  # [B, H]

    def one_pass(t, carry):
        x, k_cache, v_cache, state = carry

        def layer(carry, xs):
            lp, li = xs
            return mistral._token_layer(
                cfg, rope, attn_backend, row, carry, lp,
                t * cfg.num_layers + li, None, scope=jax.named_scope,
            ), None

        (x, k_cache, v_cache), _ = jax.lax.scan(
            layer, (x, k_cache, v_cache), (params['layers'], layers),
            unroll=cfg.num_layers,
        )
        z, state, _ = _close_pass(params, cfg, t, x, state)
        return z, k_cache, v_cache, state

    _, k_cache, v_cache, state = jax.lax.fori_loop(
        0, cfg.total_ut_steps, one_pass, (x, k_cache, v_cache, _exit_start(x))
    )
    passes = jnp.arange(cfg.total_ut_steps, dtype=jnp.int32)
    read = (state[4][:, None] == passes[None, :]) & live[:, None]
    return (
        logits(params, cfg, state[0]), (k_cache, v_cache),
        jnp.sum(read, axis=0, dtype=jnp.int32),
    )


def decode_loop(  # distlint: traced
    params: dict,
    cfg: OuroConfig,
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
):
    """``mistral.decode_loop``'s contract over the planes of every pass.
    Returns ``(tokens [num_steps, B], k_cache, v_cache, last_ids,
    counters)``; ``counters`` is ``{'loop_exit_pass': int32 [T]}``, the
    tokens of live rows whose head read each pass, summed over the
    window's steps."""
    rope = mistral._rope_tables(
        cfg, max_table_positions or cfg.max_position_embeddings
    )
    tokens, (k_cache, v_cache), ids, exit_pass = common.decode_window(
        functools.partial(_decode_core, params, cfg, rope, attn_backend),
        input_ids, positions, context_lens, (k_cache, v_cache),
        block_tables, steps_left, temperature, top_p, min_p, top_k, seeds,
        num_steps=num_steps, sampling_top_window=sampling_top_window,
        counts=jnp.zeros((cfg.total_ut_steps,), jnp.int32),
    )
    return tokens, k_cache, v_cache, ids, {'loop_exit_pass': exit_pass}
