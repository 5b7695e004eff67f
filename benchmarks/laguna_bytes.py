"""The least bytes one decode step of a ``laguna`` configuration moves, and
the bytes its paged-attention kernel is asked to read, computed from the
configuration's file: the weights held on the chip once (every layer's
attention with its gate, the dense layer's MLP, every sparse layer's router,
shared expert and HELD experts, and the held columns of the untied head; the
embedding is gathered by row and left out), the K and V of the rows' whole
contexts in the full-attention layers, and the K and V of at most the last
``sliding_window`` tokens in the window layers. bf16 weights and KV.
"""

from __future__ import annotations

_KIND = {'full_attention': 'full', 'sliding_attention': 'window'}


def layers_of(model: dict) -> dict:
    """``{'full': n, 'window': m}``: layers of each cache group."""
    kinds = [_KIND[t] for t in model['layer_types']]
    return {kind: kinds.count(kind) for kind in ('full', 'window')}


def weight_params(model: dict) -> int:
    """Parameters one decode step reads: all that the chip holds but the
    embedding."""
    h, d = model['hidden_size'], model['head_dim']
    kv_out = model['num_key_value_heads'] * d
    attention = sum(
        h * heads * d * 2 + h * heads + 2 * h * kv_out + h
        for heads in model['num_attention_heads_per_layer']
    )
    dense = 3 * h * model['intermediate_size'] + h
    sparse = (
        h * model.get('num_routed_experts', model['num_experts'])
        + 3 * h * model['shared_expert_intermediate_size']
        + model['num_experts'] * 3 * h * model['moe_intermediate_size'] + h
    )
    mlp = sum(
        dense if kind == 'dense' else sparse
        for kind in model['mlp_layer_types']
    )
    return attention + mlp + h * model['vocab_size'] + h


def kv_bytes_per_token_layer(model: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one cached token holds in one layer."""
    return 2 * model['num_key_value_heads'] * model['head_dim'] * dtype_bytes


def kv_bytes(model: dict, full_tokens: float, window_tokens: float) -> float:
    """Bytes of K and V behind ``full_tokens`` cached tokens of the full
    group and ``window_tokens`` of the window group (each summed over the
    rows): every layer of the group holds its own."""
    layers = layers_of(model)
    return float(kv_bytes_per_token_layer(model) * (
        layers['full'] * full_tokens + layers['window'] * window_tokens
    ))


def decode_step_bytes(
    model: dict, full_tokens: float, window_tokens: float
) -> float:
    """Held weights once, the K and V of the rows' contexts in the full
    layers and of what their windows hold in the window layers."""
    return 2.0 * weight_params(model) + kv_bytes(
        model, full_tokens, window_tokens
    )
