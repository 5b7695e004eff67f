"""The least bytes one FORWARD of an ``sdar_moe`` configuration moves in a
decode window, and the bytes and operations its paged-attention kernel is
asked for, computed from the configuration's file. A forward is one pass of
every live row's block of ``block_length`` positions: the weights held on the
chip once (every layer's attention, two head norms, router, two norms and HELD
experts, the final norm; the embedding is gathered by row and left out), the
untied head in a denoise forward and not in the committing one, the K and V of
the rows' whole contexts once in every layer, and the block's rows each layer
writes. bf16 weights and KV.
"""

from __future__ import annotations


def block_of(model: dict) -> int:
    return int(model['block_length'])


def forwards_a_block(model: dict) -> int:
    """Denoise forwards and the one that commits the block's K/V."""
    return int(model['engine']['denoise_steps']) + 1


def forwards_a_window(model: dict) -> int:
    return (
        model['engine']['decode_steps'] // block_of(model)
    ) * forwards_a_block(model)


def layer_params(model: dict) -> int:
    """Parameters of one layer as this chip holds it."""
    h, d = model['hidden_size'], model['head_dim']
    q_out = model['num_attention_heads'] * d
    kv_out = model['num_key_value_heads'] * d
    return (
        2 * h * q_out + 2 * h * kv_out + 2 * h + 2 * d  # attention, four norms
        + h * model.get('num_routed_experts', model['num_experts'])
        + model['num_experts'] * 3 * h * model['moe_intermediate_size']
    )


def head_params(model: dict) -> int:
    return model['hidden_size'] * model['vocab_size']


def weight_params(model: dict) -> float:
    """Parameters a forward reads, the mean over a block's forwards: the
    layers and the final norm every forward, the head in the denoise
    forwards alone."""
    denoise = forwards_a_block(model) - 1
    return (
        model['num_hidden_layers'] * layer_params(model) + model['hidden_size']
        + head_params(model) * denoise / forwards_a_block(model)
    )


def kv_bytes_per_token_layer(model: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one cached token holds in one layer."""
    return 2 * model['num_key_value_heads'] * model['head_dim'] * dtype_bytes


def kv_bytes(model: dict, tokens: float) -> float:
    """Bytes of K and V behind ``tokens`` cached tokens (summed over the
    rows) in every layer: what one forward's attention reads."""
    return float(
        model['num_hidden_layers'] * kv_bytes_per_token_layer(model) * tokens
    )


def attn_flops(model: dict, tokens: float) -> float:
    """Operations of one forward's attention over those cached tokens: every
    query head of every position of the block with a key and with a value, 2
    x (d + d) a head a position a token a layer (a block folded into the
    group: ``block_length x heads / kv_heads`` = 32 queries share a KV head's
    bytes, not its operations)."""
    per_token = 4 * model['num_attention_heads'] * model['head_dim'] * block_of(model)
    return float(model['num_hidden_layers'] * per_token * tokens)


def forward_bytes(model: dict, tokens: float, rows: float = 0.0) -> float:
    """Held weights once, the K and V of the rows' contexts, and the K and V
    rows each of ``rows`` rows' block writes in every layer."""
    written = rows * block_of(model) * model['num_hidden_layers'] * (
        kv_bytes_per_token_layer(model)
    )
    return 2.0 * weight_params(model) + kv_bytes(model, tokens) + written
