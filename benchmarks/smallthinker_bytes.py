"""The least bytes one decode step of a ``smallthinker`` configuration moves,
and the bytes and operations its paged-attention kernel is asked for,
computed from the configuration's file: the weights held on the chip once
(every layer's attention, router, two norms and HELD experts, the final norm
and the untied head; the embedding is gathered by row and left out), the K
and V of the rows' whole contexts in the full-attention layers, of at most
the last ``sliding_window_size`` tokens in the window layers, and the rows
each layer writes. bf16 weights and KV.
"""

from __future__ import annotations


def layers_of(model: dict) -> dict:
    """``{'full': n, 'window': m}``: layers of each cache group."""
    windowed = sum(model['sliding_window_layout'])
    return {'full': model['num_hidden_layers'] - windowed, 'window': windowed}


def weight_params(model: dict) -> int:
    """Parameters one decode step reads: all that the chip holds but the
    embedding."""
    h, d = model['hidden_size'], model['head_dim']
    q_out = model['num_attention_heads'] * d
    kv_out = model['num_key_value_heads'] * d
    layer = (
        2 * h * q_out + 2 * h * kv_out + 2 * h  # attention, the two norms
        + h * model.get('num_routed_experts', model['moe_num_primary_experts'])
        + model['moe_num_primary_experts'] * 3 * h * model['moe_ffn_hidden_size']
    )
    return model['num_hidden_layers'] * layer + h * model['vocab_size'] + h


def kv_bytes_per_token_layer(model: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one cached token holds in one layer."""
    return 2 * model['num_key_value_heads'] * model['head_dim'] * dtype_bytes


def kv_bytes(model: dict, full_tokens: float, window_tokens: float) -> float:
    """Bytes of K and V behind ``full_tokens`` cached tokens of the full
    group and ``window_tokens`` of the window group (each summed over the
    rows, a window row's counted to ``min(context, window)``: what its
    table names): every layer of the group holds its own."""
    layers = layers_of(model)
    return float(kv_bytes_per_token_layer(model) * (
        layers['full'] * full_tokens + layers['window'] * window_tokens
    ))


def attn_flops(model: dict, full_tokens: float, window_tokens: float) -> float:
    """Operations of a decode step's attention over those cached tokens:
    every query head's product with a key and with a value, 2 x (d + d) a
    head a token a layer (7 query heads share a KV head's bytes, not its
    operations)."""
    layers = layers_of(model)
    per_token = 4 * model['num_attention_heads'] * model['head_dim']
    return float(per_token * (
        layers['full'] * full_tokens + layers['window'] * window_tokens
    ))


def decode_step_bytes(
    model: dict, full_tokens: float, window_tokens: float, rows: float = 0.0
) -> float:
    """Held weights once, the K and V of the rows' contexts in the full
    layers and of what their windows hold in the window layers, and the K
    and V row each of ``rows`` rows writes in every layer."""
    written = rows * model['num_hidden_layers'] * kv_bytes_per_token_layer(model)
    return 2.0 * weight_params(model) + kv_bytes(
        model, full_tokens, window_tokens
    ) + written
