"""Mixtral-family sparse-MoE decoder with expert parallelism.

The reference has **no** MoE models (SURVEY.md §2.5: "Expert parallel —
absent"); this family makes the ``expert`` mesh axis real: expert weights
``[E, H, I]`` shard over it (``param_specs``), and because routing is
expressed as dense einsums over the expert dimension, pjit partitions the
expert-parallel compute and inserts the psum combine automatically — the
XLA-native formulation of EP (no hand-written all_to_all dispatch needed at
this scale; token-dropping capacity routing can slot in later without
changing the interface).

Architecture: Mistral backbone (GQA + RoPE + RMSNorm) with the SwiGLU MLP
replaced by a top-k-routed bank of expert MLPs (softmax-renormalized gate
weights over the selected experts, HF ``MixtralSparseMoeBlock`` semantics).
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distllm_tpu.models import common
from distllm_tpu.utils import BaseConfig


class MixtralConfig(BaseConfig):
    name: Literal['mixtral'] = 'mixtral'
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None
    intermediate_size: int = 14336
    num_experts: int = 8
    experts_per_token: int = 2
    max_position_embeddings: int = 32768
    rope_theta: float = 1e6
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-5
    sliding_window: int | None = None
    tie_word_embeddings: bool = False
    # Pinned quantized-matmul tier (see MistralConfig.qmm_backend).
    qmm_backend: str | None = None
    dtype: str = 'bfloat16'

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def cache_spec(self) -> common.CacheSpec:
        """Served by ``models/mistral.py``'s programs, as ``MistralConfig``."""
        return common.CacheSpec(
            paged=(common.PagedGroup('kv', self.num_layers),),
            programs='distllm_tpu.models.mistral',
        )

    @classmethod
    def from_hf_config(cls, hf: dict) -> 'MixtralConfig':
        return cls(
            vocab_size=hf['vocab_size'],
            hidden_size=hf['hidden_size'],
            num_layers=hf['num_hidden_layers'],
            num_heads=hf['num_attention_heads'],
            num_kv_heads=hf.get('num_key_value_heads', hf['num_attention_heads']),
            intermediate_size=hf['intermediate_size'],
            num_experts=hf.get('num_local_experts', 8),
            experts_per_token=hf.get('num_experts_per_tok', 2),
            max_position_embeddings=hf.get('max_position_embeddings', 32768),
            rope_theta=hf.get('rope_theta', 1e6),
            rope_scaling=hf.get('rope_scaling'),
            rms_norm_eps=hf.get('rms_norm_eps', 1e-5),
            sliding_window=hf.get('sliding_window'),
            tie_word_embeddings=hf.get('tie_word_embeddings', False),
        )


def init(rng: jax.Array, cfg: MixtralConfig) -> dict:
    h, hd = cfg.hidden_size, cfg.head_size
    q_out, kv_out = cfg.num_heads * hd, cfg.num_kv_heads * hd
    i, e = cfg.intermediate_size, cfg.num_experts
    scale = 0.02

    def normal(key, shape):
        return np.asarray(jax.random.normal(key, shape) * scale, np.float32)

    keys = jax.random.split(rng, 3)
    layers = []
    for li in range(cfg.num_layers):
        ks = jax.random.split(jax.random.fold_in(keys[0], li), 8)
        layers.append(
            {
                'q': {'kernel': normal(ks[0], (h, q_out))},
                'k': {'kernel': normal(ks[1], (h, kv_out))},
                'v': {'kernel': normal(ks[2], (h, kv_out))},
                'o': {'kernel': normal(ks[3], (q_out, h))},
                'attn_ln': {'scale': np.ones((h,), np.float32)},
                'router': {'kernel': normal(ks[4], (h, e))},
                'gate': {'kernel': normal(ks[5], (e, h, i))},
                'up': {'kernel': normal(ks[6], (e, h, i))},
                'down': {'kernel': normal(ks[7], (e, i, h))},
                'mlp_ln': {'scale': np.ones((h,), np.float32)},
            }
        )
    params = {
        'embed': normal(keys[1], (cfg.vocab_size, h)),
        'layers': common.stack_layers(layers),
        'final_ln': {'scale': np.ones((h,), np.float32)},
    }
    if not cfg.tie_word_embeddings:
        params['lm_head'] = normal(keys[2], (h, cfg.vocab_size))
    return params


def moe_mlp(  # distlint: traced
    x: jnp.ndarray,  # [B, S, H]
    router_kernel: jnp.ndarray,  # [H, E]
    gate: jnp.ndarray,  # [E, H, I]
    up: jnp.ndarray,  # [E, H, I]
    down: jnp.ndarray,  # [E, I, H]
    experts_per_token: int,
) -> jnp.ndarray:
    """Top-k routed SwiGLU expert bank (HF Mixtral semantics).

    Router logits → softmax over ALL experts → keep top-k per token →
    renormalize the kept weights. Compute runs as dense einsums over the
    expert dim with the combine weights zeroed for unselected experts:
    under pjit with ``[E, ...]`` weights sharded over the ``expert`` axis,
    each chip computes only its experts and the final einsum psums the
    combine — expert parallelism as XLA sees it.
    """
    dtype = x.dtype

    def deq(w):
        # Expert banks may arrive weight-only quantized (QTensor); the
        # dequant happens HERE, at point of use — inside the layer loop,
        # so only one layer's experts materialize as floats at a time
        # (same policy as common.dense).
        return (w.dequantize() if hasattr(w, 'dequantize') else w).astype(
            dtype
        )

    gate, up, down = deq(gate), deq(up), deq(down)
    logits = jnp.einsum('bsh,he->bse', x.astype(jnp.float32), router_kernel.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # [B, S, E]
    top_w, top_idx = jax.lax.top_k(probs, experts_per_token)
    top_w = top_w / jnp.clip(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # Scatter the kept weights back to a dense [B, S, E] combine matrix.
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, probs.shape[-1], dtype=jnp.float32)
        * top_w[..., None],
        axis=-2,
    )
    hidden = jnp.einsum('bsh,ehi->besi', x, gate)
    hidden = jax.nn.silu(hidden) * jnp.einsum('bsh,ehi->besi', x, up)
    expert_out = jnp.einsum('besi,eih->besh', hidden, down)
    return jnp.einsum(
        'besh,bse->bsh', expert_out, combine.astype(dtype)
    )


def apply(
    params: dict,
    cfg: MixtralConfig,
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray,
    *,
    mesh=None,
    seq_parallel: str | None = None,
) -> jnp.ndarray:
    """Dense causal forward: ``[B, S]`` → last hidden states ``[B, S, H]``.

    Delegates to the shared family forward (``models/mistral.py
    _forward``), which dispatches the MLP block on pytree structure
    (``_mlp_block`` sees the router and runs :func:`moe_mlp`) — one
    implementation for masks (incl. sliding window), RoPE, GQA attention,
    and ``seq_parallel`` ring/Ulysses, so the families cannot drift.
    """
    from distllm_tpu.models import mistral

    return mistral.apply(
        params, cfg, input_ids, attention_mask,
        mesh=mesh, seq_parallel=seq_parallel,
    )


def logits(params: dict, cfg: MixtralConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_word_embeddings or 'lm_head' not in params:
        kernel = jnp.asarray(params['embed']).T
    else:
        kernel = jnp.asarray(params['lm_head'])
    return common.dense(
        hidden, kernel, qmm_backend=getattr(cfg, 'qmm_backend', None)
    ).astype(jnp.float32)


def prefill(params: dict, cfg: MixtralConfig, input_ids, attention_mask):
    """Serving prefill — the shared machinery in :mod:`.mistral` handles
    MoE layers by pytree structure (``_mlp_block``), so Mixtral serves
    through the same paged engine (the reference's vLLM serves both
    families through one engine as well)."""
    from distllm_tpu.models import mistral

    return mistral.prefill(params, cfg, input_ids, attention_mask)


def decode_step(params: dict, cfg: MixtralConfig, *args, **kwargs):
    from distllm_tpu.models import mistral

    return mistral.decode_step(params, cfg, *args, **kwargs)


def decode_loop(params: dict, cfg: MixtralConfig, *args, **kwargs):
    from distllm_tpu.models import mistral

    return mistral.decode_loop(params, cfg, *args, **kwargs)


def param_specs(cfg: MixtralConfig, params: dict | None = None) -> dict:
    """EP x TP sharding: expert banks over ``expert``, widths over ``model``."""
    col = {'kernel': P(None, None, 'model')}
    row = {'kernel': P(None, 'model', None)}
    specs = {
        'embed': P(None, None),
        'layers': {
            'q': dict(col),
            'k': dict(col),
            'v': dict(col),
            'o': dict(row),
            'attn_ln': {'scale': P(None)},
            'router': {'kernel': P(None, None, None)},
            # [L, E, H, I]: experts over 'expert', MLP width over 'model'.
            'gate': {'kernel': P(None, 'expert', None, 'model')},
            'up': {'kernel': P(None, 'expert', None, 'model')},
            'down': {'kernel': P(None, 'expert', 'model', None)},
            'mlp_ln': {'scale': P(None)},
        },
        'final_ln': {'scale': P()},
    }
    has_lm_head = (
        'lm_head' in params if params is not None else not cfg.tie_word_embeddings
    )
    if has_lm_head:
        specs['lm_head'] = P(None, 'model')
    return specs


def params_from_hf(state: dict[str, np.ndarray], cfg: MixtralConfig) -> dict:
    """Convert HF ``MixtralForCausalLM`` weights (experts stacked on E)."""
    sd = {k.removeprefix('model.'): v for k, v in state.items()}

    def lin(key):
        return {'kernel': np.ascontiguousarray(sd[key].T)}

    def expert_stack(layer: int, proj: str) -> np.ndarray:
        # HF names: layers.{L}.block_sparse_moe.experts.{E}.w1/w3/w2
        return np.stack(
            [
                np.ascontiguousarray(
                    sd[f'layers.{layer}.block_sparse_moe.experts.{e}.{proj}.weight'].T
                )
                for e in range(cfg.num_experts)
            ]
        )

    layers = []
    for i in range(cfg.num_layers):
        p = f'layers.{i}'
        layers.append(
            {
                'q': lin(f'{p}.self_attn.q_proj.weight'),
                'k': lin(f'{p}.self_attn.k_proj.weight'),
                'v': lin(f'{p}.self_attn.v_proj.weight'),
                'o': lin(f'{p}.self_attn.o_proj.weight'),
                'attn_ln': {'scale': sd[f'{p}.input_layernorm.weight']},
                'router': lin(f'{p}.block_sparse_moe.gate.weight'),
                'gate': {'kernel': expert_stack(i, 'w1')},
                'up': {'kernel': expert_stack(i, 'w3')},
                'down': {'kernel': expert_stack(i, 'w2')},
                'mlp_ln': {'scale': sd[f'{p}.post_attention_layernorm.weight']},
            }
        )
    params = {
        'embed': sd['embed_tokens.weight'],
        'layers': common.stack_layers(layers),
        'final_ln': {'scale': sd['norm.weight']},
    }
    if 'lm_head.weight' in state and not cfg.tie_word_embeddings:
        params['lm_head'] = np.ascontiguousarray(state['lm_head.weight'].T)
    return params
